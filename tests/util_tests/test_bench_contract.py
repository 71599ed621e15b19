"""The contract for every bench script: print exactly ONE JSON line
with {"metric", "value", "unit", "vs_baseline"} and exit 0 for a live
measurement; for a child that produced no result, print the one-line
diagnosis (value=null + "error") and exit NON-zero — a failed bench is
never green and never an earlier run's number.

Unpinned runs append their successes to the run history
(``BENCH_HISTORY.json``) for the ``--check`` sentinel.  Pinned-platform
runs (all the smoke tests here) never touch it — a toy CPU number must
not sit in the history a hardware run is scored against."""

import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))


def _run(script, args, timeout=280):
    env = dict(os.environ)
    for k in list(env):
        if k.startswith(("TPU_", "LIBTPU", "PJRT_", "JAX_", "XLA_")):
            env.pop(k)
    env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, script), *args],
        capture_output=True, text=True, timeout=timeout, cwd=_ROOT,
        env=env)
    return proc


def _assert_contract(proc, expect_value):
    assert (proc.returncode == 0) == expect_value, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, f"expected ONE JSON line, got: {lines}"
    rec = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in rec, rec
    if expect_value:
        assert rec["value"] is not None and rec["value"] > 0, rec
    else:
        assert rec["value"] is None and "error" in rec, rec
    return rec


def test_bench_failure_prints_diagnosis_and_exits_nonzero():
    # an unknown platform makes the child crash fast; the parent must
    # emit the one-line diagnosis and exit non-zero, also without
    # --check (``_bench_common``'s parent is what is tested; the
    # script is only the vehicle)
    rec = _assert_contract(
        _run("bench_programs.py",
             ["--platform", "definitely-not-a-backend",
              "--timeouts", "120"]),
        expect_value=False)
    assert "attempt" in rec["error"]


def test_peak_flops_refuses_unknown_device():
    sys.path.insert(0, _ROOT)
    try:
        import _bench_common as bc
    finally:
        sys.path.pop(0)
    assert bc.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="unknown"):
        bc.peak_flops("unknown")


@pytest.mark.parametrize("script,args,unit", [
    ("bench_decode.py",
     ["--batch", "2", "--max-len", "32", "--n-layers", "1",
      "--d-model", "64", "--warmup", "0", "--iters", "1"], "tokens/sec"),
    ("bench_seq2seq.py",
     ["--batch", "8", "--vocab", "64", "--units", "16", "--max-src", "8",
      "--max-tgt", "8", "--warmup", "0", "--iters", "1",
      "--steps-per-call", "2"], "tokens/sec"),
    ("bench_resilience.py",
     ["--batch", "64", "--dim", "32", "--hidden", "64", "--warmup", "1",
      "--iters", "4", "--rounds", "1"], "%"),
    ("bench_metrics_registry.py",
     ["--batch", "8", "--dim", "64", "--hidden", "128", "--warmup", "1",
      "--iters", "4", "--rounds", "1"], "x"),
    # one prompt length and one budget (overload, fleet): these two
    # check every request against an eager solo decode, which compiles
    # each of its ops again at every new prompt and cache length (22 of
    # overload's 27 s alone, 14 of fleet's 21), and the contract reads
    # neither
    ("bench_overload.py",
     ["--requests", "12", "--slots", "8", "--horizon", "128",
      "--min-prompt", "16", "--max-prompt", "16", "--block", "8",
      "--min-new", "24",
      "--max-new", "24", "--round-tokens", "2", "--d-model", "32",
      "--n-layers", "1", "--heads", "2", "--vocab", "64",
      "--rounds", "1"], "x"),
    ("bench_fleet.py",
     ["--replicas", "2", "--requests", "12", "--slots", "8",
      "--horizon", "128", "--max-prompt", "40", "--block", "8",
      "--shared-prefixes", "2", "--shared-prefix", "16",
      "--max-suffix", "1", "--min-new", "16", "--max-new", "16",
      "--round-tokens", "2", "--arrival-ms", "2.0",
      "--kill-at-step", "2", "--d-model", "32", "--n-layers", "1",
      "--heads", "2", "--vocab", "64", "--rounds", "1"], "x"),
    ("bench_elastic.py",
     ["--dim", "64", "--hidden", "64", "--batch", "16",
      "--rounds", "1"], "x"),
    ("bench_live_elastic.py",
     ["--dim", "64", "--hidden", "64", "--batch", "16",
      "--iters", "3", "--rounds", "1"], "x"),
    ("bench_obs_plane.py",
     ["--requests", "8", "--slots", "8", "--horizon", "128",
      "--max-prompt", "16", "--block", "8", "--min-new", "4",
      "--max-new", "12", "--round-tokens", "2", "--rounds", "1",
      "--reps", "1"], "x"),
    ("bench_programs.py",
     ["--batch", "8", "--dim", "64", "--hidden", "128", "--warmup",
      "1", "--iters", "4", "--rounds", "1"], "x"),
], ids=["decode", "seq2seq", "resilience", "metrics_registry",
        "overload", "fleet", "elastic", "live_elastic", "obs_plane",
        "programs"])
def test_other_benches_contract(script, args, unit):
    rec = _assert_contract(
        _run(script, ["--platform", "cpu", *args, "--timeouts", "240"]),
        expect_value=True)
    assert rec["unit"] == unit


def test_serving_decode_tier_arms_contract():
    """The serving bench's contract row (ONE child covers the generic
    one-JSON-line contract, the ISSUE 14 decode-tier arms —
    prefix-share, sampled, speculative — AND the ragged-round arms:
    short-prompt TTFT independence under long-prompt co-admission,
    in-engine per-row speculation): exactness witnesses all zero,
    rates within range, self-draft acceptance exactly 1 (the
    machinery sanity anchor), and the in-run TTFT-independence assert
    must have held for the child to emit its line at all."""
    rec = _assert_contract(
        _run("bench_serving.py",
             ["--platform", "cpu", "--requests", "8", "--slots", "8",
              "--horizon", "128", "--max-prompt", "16", "--block", "8",
              "--min-new", "4", "--max-new", "24", "--round-tokens",
              "2", "--d-model", "32", "--n-layers", "1", "--heads",
              "2", "--vocab", "64", "--rounds", "1", "--decode-tier",
              "1", "--prefix-requests", "8", "--shared-prefix", "8",
              "--spec-prompts", "2", "--spec-new", "16",
              "--ragged-tier", "1", "--ragged-requests", "6",
              "--long-prompt", "48", "--ttft-noise-bar", "3.0",
              "--timeouts", "240"]),
        expect_value=True)
    for field in ("prefix_prefill_speedup", "prefix_hit_rate",
                  "prefix_pool_pressure_drop",
                  "prefix_share_peak_row_blocks",
                  "sampled_tokens_per_sec", "spec_tokens_per_sec",
                  "spec_acceptance_rate", "spec_vs_target_only",
                  "spec_selfdraft_acceptance_rate",
                  "ragged_short_ttft_solo_p50_ms",
                  "ragged_short_ttft_coadmit_p50_ms",
                  "lockstep_short_ttft_coadmit_p50_ms",
                  "ragged_ttft_coadmit_ratio",
                  "ragged_vs_lockstep_short_ttft",
                  "engine_spec_tokens_per_sec",
                  "engine_spec_vs_plain",
                  "engine_spec_acceptance_rate"):
        assert field in rec, field
    # the exactness ladder's bench-side witnesses
    assert rec["prefix_token_identity_mismatches"] == 0
    assert rec["sampled_replay_mismatches"] == 0
    assert rec["spec_identity_mismatches"] == 0
    assert rec["spec_selfdraft_identity_mismatches"] == 0
    assert rec["spec_selfdraft_acceptance_rate"] == 1.0
    assert 0.0 <= rec["prefix_hit_rate"] <= 1.0
    # ragged arms: per-row speculation may not move a token, long
    # co-admits staged through the chunk path
    assert rec["engine_spec_identity_mismatches"] == 0
    assert 0.0 <= rec["engine_spec_acceptance_rate"] <= 1.0
    assert rec["ragged_chunk_prefills"] >= 1


def test_decode_analyze_only_hbm_floor():
    """bench_decode --analyze-only: the analytic HBM decode floor
    behind SERVING.md's lever yardsticks — four quantization arms,
    int8 arms strictly faster (less HBM), parameter count matching
    the real initialized model's (pinned against the measured run's
    recorded n_params), and bytes consistent with the reported
    floor."""
    import json
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "bench_decode.py", "--analyze-only"],
        capture_output=True, text=True, timeout=280, cwd=_ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]
    assert len(recs) == 4
    by = {(r["int8"], r["kv_int8"]): r for r in recs}
    fp = by[(False, False)]
    assert fp["metric"] == "transformer_decode_hbm_floor_tokens_per_sec"
    # the eval_shape-derived parameter count equals the real model's
    # (the value the measured bench rows record)
    assert fp["n_params"] == 120_865_792
    # quantization strictly raises the floor, weights > cache at this
    # short context
    assert by[(True, False)]["value"] > fp["value"]
    assert by[(True, True)]["value"] > by[(True, False)]["value"]
    assert by[(False, True)]["value"] > fp["value"]
    assert fp["weight_bytes_gb"] > fp["cache_bytes_per_step_gb"]
    # floor arithmetic self-consistent: tokens/s = batch / step time
    step_s = (fp["weight_bytes_gb"] + fp["cache_bytes_per_step_gb"]) \
        / fp["hbm_gbps"]
    assert fp["value"] == pytest.approx(fp["batch"] / step_s, rel=0.01)
