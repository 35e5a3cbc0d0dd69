"""CLI parity tests: stdout contract of main.cu:166-218 (SURVEY §7 'Exact CLI parity')."""

import json
import pytest
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

EXPECTED_REFERENCE_STDOUT = (
    "Input Data:\n"
    "Hello World EveryOne\n"
    "World Good News\n"
    "Good Morning Hello\n"
    "--------------------------\n"
    "Hello\t2\n"
    "World\t2\n"
    "EveryOne\t1\n"
    "Good\t2\n"
    "News\t1\n"
    "Morning\t1\n"
    "--------------------------\n"
    "Total Count:9\n"
)


def _run(args, cwd=REPO):
    return subprocess.run(
        [sys.executable, str(REPO / "main"), *args],
        capture_output=True, text=True, cwd=cwd,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": "/root"},
    )


@pytest.mark.smoke
def test_reference_stdout_parity(tmp_path):
    fixture = tmp_path / "test.txt"
    fixture.write_text("Hello World EveryOne\nWorld Good News\nGood Morning Hello\n")
    r = _run([str(fixture)])
    assert r.returncode == 0, r.stderr
    assert r.stdout == EXPECTED_REFERENCE_STDOUT


def test_default_filename_is_test_txt(tmp_path):
    """argv-less run reads ./test.txt, matching the hardcoded name (main.cu:167)."""
    (tmp_path / "test.txt").write_text("a b a\n")
    r = _run([], cwd=tmp_path)
    assert r.returncode == 0, r.stderr
    assert "a\t2" in r.stdout and "Total Count:3" in r.stdout


@pytest.mark.smoke
def test_missing_file_is_an_error(tmp_path):
    """The reference silently prints an empty result on fopen failure
    (main.cu:174); we surface the failure (SURVEY §5 failure detection)."""
    r = _run([str(tmp_path / "nope.txt")])
    assert r.returncode == 2
    assert "cannot read" in r.stderr


@pytest.mark.smoke
def test_json_format(tmp_path):
    f = tmp_path / "in.txt"
    f.write_text("x y x z\n")
    r = _run([str(f), "--format", "json"])
    assert r.returncode == 0, r.stderr
    obj = json.loads(r.stdout)
    assert obj["counts"] == [["x", 2], ["y", 1], ["z", 1]]
    assert obj["total"] == 4 and obj["distinct"] == 3


def test_json_distinct_bytes_stay_distinct(tmp_path):
    """Two invalid-UTF8 byte words must not collapse into one JSON entry."""
    f = tmp_path / "in.bin"
    f.write_bytes(b"\xff \xfe\n")
    r = _run([str(f), "--format", "json"])
    assert r.returncode == 0, r.stderr
    obj = json.loads(r.stdout)
    assert len(obj["counts"]) == 2 and obj["distinct"] == 2


def test_bad_chunk_bytes_is_clean_error(tmp_path):
    f = tmp_path / "in.txt"
    f.write_text("a\n")
    r = _run([str(f), "--chunk-bytes", "1000"])
    assert r.returncode == 2
    assert "chunk_bytes" in r.stderr and "Traceback" not in r.stderr


@pytest.mark.smoke
def test_top_k(tmp_path):
    f = tmp_path / "in.txt"
    f.write_text("a a a b b c\n")
    r = _run([str(f), "--top-k", "2", "--format", "tsv"])
    assert r.returncode == 0, r.stderr
    assert r.stdout == "a\t3\nb\t2\n"


@pytest.mark.slow
def test_max_token_bytes_flag_on_pallas_backend(tmp_path):
    """--max-token-bytes reaches the pallas config: a token longer than W is
    rescued exactly by default (ops/rescue.py), and dropped into the
    accounting with --rescue-overlong 0 (the round-3 contract)."""
    f = tmp_path / "in.txt"
    f.write_text("short " + "L" * 40 + " short\n")
    base = [str(f), "--format", "json", "--backend", "pallas",
            "--chunk-bytes", str(128 * 18), "--max-token-bytes", "8"]
    r = _run(base)
    assert r.returncode == 0, r.stderr
    obj = json.loads(r.stdout)
    assert obj["counts"] == [["short", 2], ["L" * 40, 1]]
    assert obj["total"] == 3 and obj["dropped_count"] == 0

    r = _run(base + ["--rescue-overlong", "0"])
    assert r.returncode == 0, r.stderr
    obj = json.loads(r.stdout)
    assert obj["counts"] == [["short", 2]]
    assert obj["total"] == 3 and obj["dropped_count"] == 1


def test_multiple_input_files(tmp_path):
    a, b = tmp_path / "a.txt", tmp_path / "b.txt"
    a.write_text("x y x\n")
    b.write_text("y z\n")
    for extra in ([], ["--stream", "--chunk-bytes", "1024",
                       "--table-capacity", "2048"]):
        r = _run([str(a), str(b), "--format", "json", "--no-echo"] + extra)
        assert r.returncode == 0, r.stderr
        obj = json.loads(r.stdout)
        assert dict(map(tuple, obj["counts"])) == {"x": 2, "y": 2, "z": 1}
        assert obj["total"] == 5


def test_distinct_sketch_requires_stream(tmp_path):
    """Honest failure beats a flag silently ignored: the non-stream path
    never consults the sketch."""
    f = tmp_path / "in.txt"
    f.write_text("a b\n")
    r = _run([str(f), "--distinct-sketch"])
    assert r.returncode == 2
    assert "--distinct-sketch requires --stream" in r.stderr


@pytest.mark.slow
def test_multi_file_grep_no_cross_file_seam_match(tmp_path):
    """A newline-bearing pattern must not match across the artificial seam
    between joined input files (only NUL is rejected in patterns)."""
    a, b = tmp_path / "a.txt", tmp_path / "b.txt"
    a.write_text("x b")  # no trailing newline: the old join fabricated "b\na"
    b.write_text("a y\n")
    r = _run([str(a), str(b), "--grep", "b\na", "--format", "json"])
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout)["matches"] == 0
    # Control: the same pattern in ONE file does match.
    c = tmp_path / "c.txt"
    c.write_text("x b\na y\n")
    r = _run([str(c), "--grep", "b\na", "--format", "json"])
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout)["matches"] == 1


def test_cli_fails_loudly_on_unknown_platform(tmp_path):
    """An unknown JAX platform fails the CLI at once with JAX's own error,
    never a hang and never a quiet fallback to the CPU."""
    f = tmp_path / "in.txt"
    f.write_text("a b\n")
    r = subprocess.run(
        [sys.executable, str(REPO / "main"), str(f)],
        capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "HOME": str(tmp_path),
             "JAX_PLATFORMS": "bogus_platform"},
    )
    assert r.returncode != 0
    assert "bogus_platform" in r.stderr
    assert "Total Count" not in r.stdout


def test_cpu_escape_hatch_overrides_pinned_platform_config(tmp_path):
    """JAX_PLATFORMS=cpu must reach the host CPU even when something pinned
    jax.config.jax_platforms to another platform at interpreter startup.
    Simulates such a pin, then runs the full CLI."""
    fixture = tmp_path / "test.txt"
    fixture.write_text("Hello World EveryOne\nWorld Good News\nGood Morning Hello\n")
    code = (
        "import jax\n"
        # Simulated pin: a platform that does not exist, so
        # any device use that honors the pin fails loudly.
        "jax.config.update('jax_platforms', 'nosuchplatform,cpu')\n"
        "from mapreduce_tpu.cli import main\n"
        f"raise SystemExit(main([{str(fixture)!r}]))\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "HOME": "/root",
             "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)},
    )
    assert r.returncode == 0, r.stderr
    assert "Total Count:9" in r.stdout


def test_platform_flag_forces_cpu_under_pinned_config(tmp_path):
    """--platform cpu is the flag form of the same escape hatch."""
    fixture = tmp_path / "test.txt"
    fixture.write_text("a b a\n")
    code = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'nosuchplatform,cpu')\n"
        "from mapreduce_tpu.cli import main\n"
        f"raise SystemExit(main(['--platform', 'cpu', {str(fixture)!r}]))\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "HOME": "/root",
             "PYTHONPATH": str(REPO)},  # no JAX_PLATFORMS at all
    )
    assert r.returncode == 0, r.stderr
    assert "Total Count:3" in r.stdout


def test_apply_platform_reads_config_not_env(monkeypatch):
    """_apply_platform reports the EFFECTIVE platform (jax.config), not the
    raw env var: here the env var claims an accelerator but the config
    (what JAX will actually dial) says cpu."""
    from mapreduce_tpu import cli

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    # conftest forced jax.config.jax_platforms to "cpu" for the whole suite;
    # _apply_platform must report that config value, not the env var.
    assert cli._apply_platform("auto") == "cpu"


def test_sample_zero_is_an_error(tmp_path):
    """--sample 0 must error, not silently fall through to word-count mode
    (advisor round 2: the old 0-default made an explicit 0 indistinguishable
    from the flag being absent)."""
    f = tmp_path / "in.txt"
    f.write_text("a b a\n")
    r = _run([str(f), "--sample", "0"])
    assert r.returncode == 2
    assert "--sample must be >= 1" in r.stderr
    # And a valid sample still works.
    r2 = _run([str(f), "--sample", "2", "--format", "json"])
    assert r2.returncode == 0, r2.stderr
    assert len(json.loads(r2.stdout)["sample"]) == 2


@pytest.mark.slow
def test_merge_every_flag_validation(tmp_path):
    """--merge-every must error where it would be a silent no-op: without
    --stream, with --grep/--sample, and with --ngram (pairwise combine)."""
    f = tmp_path / "in.txt"
    f.write_text("a b a\n")
    for args, msg in (
        ([str(f), "--merge-every", "4"], "requires --stream"),
        ([str(f), "--stream", "--merge-every", "4", "--grep", "a"],
         "not supported"),
        ([str(f), "--stream", "--merge-every", "4", "--sample", "1"],
         "not supported"),
        ([str(f), "--stream", "--merge-every", "4", "--ngram", "2"],
         "word-count runs only"),
    ):
        r = _run(args)
        assert r.returncode == 2, args
        assert msg in r.stderr, args
    # And the valid form still runs.
    r = _run([str(f), "--stream", "--merge-every", "2", "--format", "json"])
    assert r.returncode == 0, r.stderr
    assert '"total": 3' in r.stdout


@pytest.mark.smoke
def test_inflight_and_prefetch_depth_flags_validate(tmp_path):
    """ISSUE 5: the window knobs validate at the parser (clean exit 2
    before any device work, not a mid-run traceback).  The streamed
    pipelined-vs-serial identity itself is covered in test_executor.py —
    no subprocess compile paid here."""
    f = tmp_path / "in.txt"
    f.write_text("a b a c\n")
    for args in ([str(f), "--inflight", "0"],
                 [str(f), "--prefetch-depth", "0"]):
        r = _run(args)
        assert r.returncode == 2, args
        assert "must be >= 1" in r.stderr, args


def test_batch_ledger_without_stream(tmp_path, capsys):
    """ISSUE 8 satellite: --ledger/--metrics-out no longer require
    --stream.  A batch (single-buffer) run emits run_start + a
    result-derived data record + run_end, and the registry snapshot
    lands; the ledger classifies through obs_report's data-health path.
    In-process (no subprocess jax startup): the tier-1 budget rule."""
    import sys as _sys

    from mapreduce_tpu import cli

    _sys.path.insert(0, str(REPO / "tools"))
    try:
        import obs_report
    finally:
        _sys.path.pop(0)
    f = tmp_path / "in.txt"
    f.write_text("aa bb aa cc aa\n")
    led = tmp_path / "run.jsonl"
    met = tmp_path / "metrics.json"
    assert cli.main([str(f), "--no-echo", "--format", "json",
                     "--ledger", str(led), "--metrics-out",
                     str(met)]) == 0
    capsys.readouterr()
    recs = obs_report.read_ledger(str(led))
    assert [x["kind"] for x in recs] == ["run_start", "data", "run_end"]
    start, data, end = recs
    assert start["driver"] == "single_buffer" and start["job"] == "wordcount"
    assert start["ledger_version"] == 10
    assert data["tokens"] == 5 and data["table_valid"] == 3
    assert data["top_count"] == 3 and data["dropped_tokens"] == 0
    assert end["words"] == 5 and end["elapsed_s"] > 0
    assert json.loads(met.read_text())  # registry snapshot written
    runs = obs_report.analyze(str(led))
    assert len(runs) == 1 and runs[0]["completed"]
    # top mass 3/5: the report's data-health section classifies it.
    assert runs[0]["data_health"]["verdict"] == "skew-hot"
    # Batch grep runs bracket the ledger too (run_start/run_end; no data
    # record — grep has no table to summarize).
    g = tmp_path / "g.txt"
    g.write_text("abc abc\nxyz\n")
    gled = tmp_path / "grep.jsonl"
    assert cli.main([str(g), "--grep", "abc", "--ledger", str(gled)]) == 0
    capsys.readouterr()
    grecs = obs_report.read_ledger(str(gled))
    assert [x["kind"] for x in grecs] == ["run_start", "run_end"]
    assert grecs[0]["job"] == "grep" and grecs[1]["words"] == 2


def test_env_fault_plan_skipped_off_stream(tmp_path, capsys, monkeypatch):
    """Exporting MAPREDUCE_FAULT_PLAN to chaos-test a streamed service
    must not hard-error unrelated batch-mode invocations: the env
    default binds only to --stream runs; off-stream it warns and runs
    clean.  An EXPLICIT --fault-plan without --stream still errors."""
    from mapreduce_tpu import cli

    f = tmp_path / "in.txt"
    f.write_bytes(b"alpha beta alpha\n")
    monkeypatch.setenv("MAPREDUCE_FAULT_PLAN", "seed=1,rate=0.5")
    rc = cli.main([str(f), "--format", "json", "--no-echo"])
    out = capsys.readouterr()
    assert rc == 0, out.err
    assert "fault injection skipped" in out.err
    with pytest.raises(SystemExit) as ei:
        cli.main([str(f), "--fault-plan", "seed=1", "--format", "json",
                  "--no-echo"])
    assert ei.value.code == 2
    capsys.readouterr()


def test_preemption_exits_resumable_75(tmp_path, capsys):
    """ISSUE 15: a preemption-classed fault is an ORDERLY shutdown on the
    CLI surface — drain, checkpoint, one-line `preempted:` stderr, exit
    75 (EX_TEMPFAIL: relaunch the same command to resume) — never a
    traceback.  The relaunch-resumes-exactly half lives at the executor
    level (test_faults.test_preemption_drains_checkpoints_and_resumes);
    paying a second streamed run here would only re-prove it.
    In-process (no subprocess jax startup): the tier-1 budget rule."""
    from mapreduce_tpu import cli
    from mapreduce_tpu.runtime import checkpoint as ckpt_mod

    corpus = b"alpha beta alpha gamma beta alpha delta\n" * 300
    f = tmp_path / "in.txt"
    f.write_bytes(corpus)
    ck = tmp_path / "ck.npz"
    rc = cli.main([str(f), "--stream", "--chunk-bytes", "512",
                   "--retry", "1", "--checkpoint", str(ck),
                   "--checkpoint-every", "2", "--format", "json",
                   "--no-echo", "--fault-plan",
                   "at=dispatch:1:preemption"])
    out = capsys.readouterr()
    assert rc == 75, (rc, out.err)
    assert "preempted:" in out.err and "Traceback" not in out.err
    assert ck.exists(), "the drain must leave a resumable snapshot"
    assert ckpt_mod.verify(str(ck)) is True, \
        "the preemption snapshot must carry a passing integrity sidecar"
