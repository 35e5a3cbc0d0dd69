"""The overlong rescue through the normal path, against the plain reference.

Text from the benchmark's own generators, where 0.3% of tokens are unique
URLs of 40-306 bytes: the ``webby-wordcount`` cell's (``text8url``: words
with text8's statistics) and the older ``webby`` (English-like words,
capitals, punctuation, markup).  Each is counted by ``count_file`` on the
``pallas`` backend in interpret mode, with the configuration's
``rescue_window`` and tier budgets small enough that, at this size, most
chunks overflow the first tier and escalate to the second.  On the CPU the
``auto`` backend resolves to the XLA map, which counts long tokens without
the rescue, so the backend is named here.

The control turns the rescue off: the same file must then differ from the
reference, with the URLs in ``dropped``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from mapreduce_tpu import obs
from mapreduce_tpu.config import DEFAULT_CONFIG
from mapreduce_tpu.parallel.mesh import data_mesh
from mapreduce_tpu.runtime import executor

from conftest import pallas_interpret_mode

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SEED = 7
FILE_BYTES = 400_000
SMALL = {"chunk_bytes": 128 * 1024, "table_capacity": 1 << 15,
         "backend": "pallas", "rescue_overlong_max": 1024}


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _text(tmp_path_factory, config_file: str):
    """(path, bytes, the configuration's program settings, the plain
    reference) of one seeded file of a configuration's text."""
    # The generators and the reference import the benchmark's ``corpus``.
    if "corpus" not in sys.modules:
        _load("corpus", os.path.join(BENCH, "corpus.py"))
    with open(os.path.join(BENCH, "configs", config_file)) as f:
        config = json.load(f)
    gen = _load("webby_rescue_gen_" + config["generator"],
                os.path.join(BENCH, "generators",
                             config["generator"] + ".py"))
    data = gen.make(config["corpus"], np.random.default_rng(SEED),
                    [FILE_BYTES])[0].data
    path = tmp_path_factory.mktemp(config["generator"]) / "text.txt"
    data.tofile(path)
    reference = _load("webby_rescue_ref", os.path.join(BENCH, "reference.py"))
    return str(path), data.tobytes(), config["program"], reference


@pytest.fixture(scope="module")
def webby(tmp_path_factory):
    return _text(tmp_path_factory, "webby-wordcount.json")


@pytest.fixture(scope="module")
def text8url(tmp_path_factory):
    """The text of the ``webby-wordcount.stream`` cell."""
    return _text(tmp_path_factory, "webby-wordcount.text8url.json")


def _check(text, rescue_overlong: int) -> None:
    path, data, program, reference = text
    config = dataclasses.replace(DEFAULT_CONFIG, **{**program, **SMALL},
                                 rescue_overlong=rescue_overlong)
    assert config.rescue_window == 320
    registry = obs.registry.MetricsRegistry()
    with pallas_interpret_mode(), \
            obs.Telemetry.create(registry=registry) as tel:
        res = executor.count_file(path, config, mesh=data_mesh(1), retry=0,
                                  telemetry=tel)
    got = dict(zip(res.words, res.counts))
    expected = reference.word_counts(data)
    long_words = {w: c for w, c in expected.items()
                  if len(w) > config.pallas_max_token}
    counters = registry.snapshot()["counters"]
    overlong = counters.get("data.overlong_tokens", 0)
    assert long_words and overlong == sum(long_words.values())
    if rescue_overlong:
        assert got == expected
        assert res.total == sum(expected.values())
        assert res.dropped_count == 0 and res.dropped_uniques == 0
        assert counters.get("data.rescued_tokens", 0) == overlong > 0
        assert counters.get("data.rescue_escalations", 0) >= 1
    else:
        assert got != expected
        assert got == reference.control_counts(
            data, {"max_token_bytes": config.pallas_max_token})
        assert res.dropped_count == overlong > 0
        assert counters.get("data.rescued_tokens", 0) == 0


@pytest.mark.parametrize("rescue_overlong", [16, 0],
                         ids=["rescue", "control"])
def test_webby_counts_match_the_reference(webby, rescue_overlong):
    _check(webby, rescue_overlong)


@pytest.mark.parametrize("rescue_overlong", [16, 0],
                         ids=["rescue", "control"])
def test_text8url_counts_match_the_reference(text8url, rescue_overlong):
    _check(text8url, rescue_overlong)
