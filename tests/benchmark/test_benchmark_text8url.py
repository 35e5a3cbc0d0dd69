"""``webby-wordcount``'s generator (``generators/text8url.py``) and
configuration: expected counts against the plain reference, the fitted law
against the public counts the configuration cites, and what the text asks
of the program (a table past 2**18 slots, a chunk's URLs within the
rescue's second tier).  CPU only, small sizes."""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys

import numpy as np
import pytest

from mapreduce_tpu.config import DEFAULT_CONFIG

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import corpus  # noqa: E402
import reference  # noqa: E402

#: text8's counts, as the configuration's ``source_stats`` gives them.
TEXT8_TOKENS = 17_005_207
TEXT8_DISTINCT = 253_854
TEXT8_SEEN_5 = 71_290
TEXT8_THE = 1_061_396


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _config() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}
    with open(os.path.join(ROOT, entry["webby-wordcount"]["file"])) as f:
        return json.load(f)


CFG = _config()
gen = _load(os.path.join(BENCH, "generators", CFG["generator"] + ".py"),
            "gen_t_" + CFG["generator"])


def _expected(p: np.ndarray, n_tokens: float) -> tuple[float, float]:
    """Expected distinct ranks, and ranks seen 5 times or more, in
    ``n_tokens`` draws (each rank's count is Poisson)."""
    lam = n_tokens * p
    below_5 = np.exp(-lam) * sum(lam ** k / math.factorial(k)
                                 for k in range(5))
    return float(-np.expm1(-lam).sum()), float((1 - below_5).sum())


def test_the_cell_runs_this_generator():
    assert CFG["name"] == "webby-wordcount"
    assert CFG["generator"] == "text8url"
    assert CFG["reduced"] == {}


@pytest.mark.parametrize("seed", [0, 2**31 + 12345])
def test_counts_by_construction_match_the_reference(seed):
    rng = np.random.default_rng(seed)
    sizes = [300_000, 77_777]
    files = gen.make(CFG["corpus"], rng, sizes)
    for c, n in zip(files, sizes):
        assert len(c.data) == n
        assert reference.word_counts(c.data.tobytes()) == c.counts
    turned = corpus.rotate(files[0], rng)
    assert not np.array_equal(turned.data, files[0].data)
    assert reference.word_counts(turned.data.tobytes()) == files[0].counts


def test_same_seed_same_files():
    a, b, c = (gen.make(CFG["corpus"], np.random.default_rng(s),
                        [50_000])[0] for s in (7, 7, 8))
    assert np.array_equal(a.data, b.data)
    assert not np.array_equal(a.data, c.data)


def test_the_law_gives_text8s_counts_at_text8s_size():
    p = gen.rank_probs(CFG["corpus"])
    distinct, seen_5 = _expected(p, TEXT8_TOKENS)
    assert distinct == pytest.approx(TEXT8_DISTINCT, rel=1e-3)
    assert seen_5 == pytest.approx(TEXT8_SEEN_5, rel=1e-3)
    assert TEXT8_TOKENS * p[0] == pytest.approx(TEXT8_THE, rel=1e-3)


def test_words_are_distinct_lowercase_and_of_text8s_mean_length():
    params = CFG["corpus"]
    p = gen.rank_probs(params)
    lengths = gen.word_lengths(params, p, np.random.default_rng(1))
    assert float(p @ lengths) == pytest.approx(params["mean_word_bytes"],
                                               abs=0.01)
    assert lengths.max() <= DEFAULT_CONFIG.pallas_max_token
    words = gen._words(params, lengths, np.random.default_rng(2))
    assert len(set(words)) == len(words) == params["vocab"]
    assert all(w.isalpha() and w.islower() for w in words[::97])
    assert [len(w) for w in words[::97]] == list(lengths[::97])


def test_urls_are_unique_and_within_the_rescue_window():
    c = gen.make(CFG["corpus"], np.random.default_rng(3), [2_000_000])[0]
    long = {w: n for w, n in c.counts.items() if len(w) > 32}
    lengths = [len(w) for w in long]
    assert long and set(long.values()) == {1}
    assert all(w.startswith(b"http://") for w in long)
    assert min(lengths) >= 40 and max(lengths) <= 306
    assert max(lengths) > 191  # past DEFAULT_CONFIG's window
    assert max(lengths) < CFG["program"]["rescue_window"]
    share = len(long) / sum(c.counts.values())
    assert share == pytest.approx(CFG["corpus"]["p_url"], rel=0.15)


def test_a_job_needs_the_raised_table_and_a_chunk_fits_the_second_tier():
    """A job's distinct tokens pass the default 2**18-slot table, which is
    why the configuration raises it, and fit the table it sets; a 32 MiB
    chunk's URLs fit the rescue's second tier with room."""
    params = CFG["corpus"]
    p = gen.rank_probs(params)
    lengths = gen.word_lengths(params, p, np.random.default_rng(1))
    lo, hi = params["url_body_bytes"]
    url_bytes = (hi - lo) / math.log(hi / lo) + 8
    per_token = (1 - params["p_url"]) * (float(p @ lengths) + 1) \
        + params["p_url"] * url_bytes
    tokens = CFG["job_bytes"] / per_token
    words, _ = _expected(p, tokens * (1 - params["p_url"]))
    distinct = words + tokens * params["p_url"]
    assert DEFAULT_CONFIG.table_capacity < distinct
    assert distinct < 0.75 * CFG["program"]["table_capacity"]
    chunk_urls = DEFAULT_CONFIG.chunk_bytes / per_token * params["p_url"]
    assert chunk_urls + 6 * math.sqrt(chunk_urls) \
        < DEFAULT_CONFIG.rescue_slots_max
    assert chunk_urls > DEFAULT_CONFIG.rescue_slots  # the tier escalates


def test_control_breaks_the_guarantee():
    c = gen.make(CFG["corpus"], np.random.default_rng(5), [400_000])[0]
    control = reference.control_counts(c.data.tobytes(), CFG["control"])
    assert control != c.counts
    assert set(c.counts) - set(control) == {w for w in c.counts
                                            if len(w) > 32}


@pytest.mark.parametrize("control", [False, True],
                         ids=["program", "control"])
def test_the_cell_rehearses_from_benchmark_json(capsys, control):
    """The cell as ``BENCHMARK.json`` holds it, at the harness's CPU
    size: the program is correct, the control is not."""
    harness = _load(os.path.join(BENCH, "run.py"), "bench_run_t_text8url")
    extra = ["--control"] if control else []
    rc = harness.main(["--workload", "webby-wordcount.stream",
                       "--seed", str(2**31 + 77), "--seconds", "0.1",
                       "--rehearse", *extra])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["metrics"] == {}
    if control:
        assert result["correct"] is False
        assert result["checks"]["wrong_words"]["value"] > 0
    else:
        assert result["correct"] is True
        assert all(v["value"] == 0 for v in result["checks"].values())
