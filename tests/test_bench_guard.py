"""bench.py's streamed-over-H2D ratio and its time form.

Pure host tests: the helpers are jax-free, so these run in milliseconds and
live in tier-1.
"""

import bench


# -- the streamed metric over the H2D floor ---------------------------------


def test_streamed_ratio_of_a_bench_result():
    """The ratio reads the two legs off a whole bench result line."""
    result = {"metric": "zipf_wordcount_device_throughput", "value": 0.4276,
              "h2d_gbps": 0.0496, "streamed_ingest_gbps": 0.0028}
    assert bench._streamed_ratio(result) == round(0.0028 / 0.0496, 4)


def test_streamed_ratio_missing_or_zero_legs():
    assert bench._streamed_ratio({}) is None
    assert bench._streamed_ratio({"h2d_gbps": 0.02}) is None
    assert bench._streamed_ratio({"streamed_ingest_gbps": 0.01}) is None
    assert bench._streamed_ratio(
        {"streamed_ingest_gbps": 0.01, "h2d_gbps": 0.0}) is None
    assert bench._streamed_ratio(
        {"streamed_ingest_gbps": 0.0088, "h2d_gbps": 0.0194}) == 0.4536


def test_time_ratio_zero_and_inverse():
    """A near-hung streamed pass rounds the GB/s ratio to 0.0; the time
    form must come back None (skipped from the result), never a
    ZeroDivisionError after all the timed work."""
    assert bench._time_ratio(None) is None
    assert bench._time_ratio(0.0) is None
    assert bench._time_ratio(0.0565) == round(1.0 / 0.0565, 4)
    assert bench._time_ratio(1.0) == 1.0
