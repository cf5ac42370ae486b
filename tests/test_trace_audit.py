"""Jaxpr-backend tests: paired true-positive / near-miss fixtures per
J-rule, the engine-level audit green path, injected red paths (extra
trace after warmup; donation-miss), manifest round-trip + drift, and
the CLI gate against the committed ``tools/trace_manifest.json``.

Fixture jits are tiny lambdas traced inside a :class:`TraceAudit`
context, so each test exercises the real capture path (cache-size
delta detection + ``jitted.trace``), not hand-built entries.
"""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr import (
    ENGINE_SPECS, ConfigReport, TraceAudit, TraceEntry, audit_config,
    canonical_jaxpr, compare_manifest, gate, load_waivers,
    manifest_from_reports, run_rules,
)

REPO = Path(__file__).resolve().parent.parent
MANIFEST = REPO / "tools" / "trace_manifest.json"

F32 = jnp.float32


def capture(drive):
    """Run ``drive(audit)`` under a TraceAudit and return its entries."""
    with TraceAudit() as audit:
        drive(audit)
    return audit.entries


# ------------------------------------------------------------------ J1
@pytest.mark.filterwarnings("ignore:Some donated buffers")
def test_j1_donation_miss_fires():
    def drive(_):
        f = jax.jit(lambda x, y: (x + y).sum(), donate_argnums=(0,))
        f(jnp.ones((4,), F32), jnp.ones((4,), F32))
    fs = run_rules(capture(drive))
    assert [f.rule for f in fs] == ["J1"]
    assert "silently copy" in fs[0].message


def test_j1_matching_donation_near_miss():
    # same donation, but the output matches the donated buffer's
    # shape/dtype, so XLA aliases in place — clean
    def drive(_):
        f = jax.jit(lambda x, y: x + y, donate_argnums=(0,))
        f(jnp.ones((4,), F32), jnp.ones((4,), F32))
    assert run_rules(capture(drive)) == []


def test_j1_weak_type_does_not_block_aliasing():
    # aliasing matches on shape+dtype; a weak-typed output must still
    # count as a match for a strong-typed donated input
    def drive(_):
        f = jax.jit(lambda x: x * 2, donate_argnums=(0,))
        f(jnp.ones((8,), F32))
    assert run_rules(capture(drive)) == []


# ------------------------------------------------------------------ J2
def test_j2_debug_print_in_hot_graph_fires():
    def drive(_):
        def step(x):
            jax.debug.print("x = {}", x)
            return x * 2
        f = jax.jit(step)
        f(jnp.ones((4,), F32))
    fs = run_rules(capture(drive))
    assert any(f.rule == "J2" and "debug_print" in f.message
               for f in fs)


def test_j2_clean_graph_near_miss():
    def drive(_):
        f = jax.jit(lambda x: x * 2)
        f(jnp.ones((4,), F32))
    assert run_rules(capture(drive)) == []


# ------------------------------------------------------------------ J3
def test_j3_weak_type_key_split_fires():
    # g(array) and g(python float) differ only in weak_type: two cache
    # entries, identical computation — the wasted-compile class
    def drive(_):
        g = jax.jit(lambda x: x * 2.0)
        g(jnp.ones((), F32))
        g(1.0)
    entries = capture(drive)
    assert len(entries) == 2
    assert canonical_jaxpr(entries[0].jaxpr) == \
        canonical_jaxpr(entries[1].jaxpr)
    fs = run_rules(entries)
    assert [f.rule for f in fs] == ["J3"]
    assert "keyed apart" in fs[0].message


def test_j3_repeated_same_key_near_miss():
    # the same aval twice is ONE cache entry — nothing to dedupe
    def drive(_):
        g = jax.jit(lambda x: x * 2.0)
        g(jnp.ones((), F32))
        g(jnp.ones((), F32))
    entries = capture(drive)
    assert len(entries) == 1
    assert run_rules(entries) == []


def test_j3_redundant_static_split_fires():
    # a static arg that does not change the graph keys two identical
    # compiles apart; one that DOES change it is a legitimate split
    def drive(_):
        h = jax.jit(lambda x, flag: x + 1, static_argnames=("flag",))
        h(jnp.ones((2,), F32), flag=True)
        h(jnp.ones((2,), F32), flag=False)
    fs = run_rules(capture(drive))
    assert [f.rule for f in fs] == ["J3"]
    assert "static args" in fs[0].message


def test_j3_meaningful_static_split_near_miss():
    def drive(_):
        h = jax.jit(lambda x, flag: x + (1 if flag else 2),
                    static_argnames=("flag",))
        h(jnp.ones((2,), F32), flag=True)
        h(jnp.ones((2,), F32), flag=False)
    entries = capture(drive)
    assert len(entries) == 2
    assert run_rules(entries) == []


# ------------------------------------------------------------------ J4
def test_j4_large_captured_constant_fires():
    big = jnp.asarray(np.zeros((128, 128), np.float32))   # 64 KiB

    def drive(_):
        f = jax.jit(lambda x: x + big)
        f(jnp.zeros((128, 128), F32))
    fs = run_rules(capture(drive))
    assert any(f.rule == "J4" and "65536 bytes" in f.message for f in fs)


def test_j4_small_constant_near_miss():
    small = jnp.asarray(np.zeros((4, 4), np.float32))

    def drive(_):
        f = jax.jit(lambda x: x + small)
        f(jnp.zeros((4, 4), F32))
    assert run_rules(capture(drive)) == []


# ------------------------------------------------------------------ J5
def test_j5_post_warm_trace_fires():
    def drive(audit):
        f = jax.jit(lambda x: x * 2)
        f(jnp.ones((4,), F32))
        audit.mark_warm()
        f(jnp.ones((8,), F32))        # new shape -> new graph, post-warm
    entries = capture(drive)
    assert [e.post_warm for e in entries] == [False, True]
    fs = run_rules(entries)
    assert [f.rule for f in fs] == ["J5"]
    assert "AFTER warmup" in fs[0].message


def test_j5_warm_shape_reuse_near_miss():
    def drive(audit):
        f = jax.jit(lambda x: x * 2)
        f(jnp.ones((4,), F32))
        audit.mark_warm()
        f(jnp.ones((4,), F32))        # warm shape -> cache hit
    entries = capture(drive)
    assert len(entries) == 1 and not entries[0].post_warm
    assert run_rules(entries) == []


# ------------------------------------------------------- capture details
def test_capture_is_exact_one_entry_per_cache_entry():
    def drive(_):
        f = jax.jit(lambda x: x + 1)
        for _ in range(5):
            f(jnp.ones((4,), F32))
        f(jnp.ones((2, 2), F32))
    entries = capture(drive)
    assert len(entries) == 2


def test_signature_and_digest_are_deterministic():
    def drive(_):
        f = jax.jit(lambda x, n: x[:2] * n, static_argnames=("n",))
        f(jnp.ones((4,), F32), n=3)
    a, = capture(drive)
    b, = capture(drive)
    assert a.signature == b.signature and a.digest == b.digest
    assert "n=3" in a.static_args


# ----------------------------------------------------- engine-level audit
@pytest.fixture(scope="module")
def dense_report():
    return audit_config("dense")


def test_engine_audit_green(dense_report):
    # the acceptance criterion: a real engine build compiles everything
    # in warmup and violates no J-rule
    assert dense_report.findings == []
    assert all(not e.post_warm for e in dense_report.entries)
    assert dense_report.entries, "audit captured no graphs"


def test_engine_entries_carry_registry_labels(dense_report):
    labels = {e.label for e in dense_report.entries}
    assert labels <= set(dense_report.trace_counts)
    assert "paged_decode" in labels     # the engine's decode plane


def test_engine_audit_matches_committed_manifest(dense_report):
    manifest = json.loads(MANIFEST.read_text())
    manifest["configs"] = {"dense": manifest["configs"]["dense"]}
    assert gate({"dense": dense_report}, manifest) == []


def test_injected_extra_trace_turns_gate_red():
    def inject(_srv, _audit):
        f = jax.jit(lambda x: x * 3)
        f(jnp.ones((5,), F32))        # post-warm compile stall
    rep = audit_config("dense", mutate=inject)
    manifest = json.loads(MANIFEST.read_text())
    manifest["configs"] = {"dense": manifest["configs"]["dense"]}
    fs = gate({"dense": rep}, manifest)
    assert any(f.rule == "J5" and "AFTER warmup" in f.message
               for f in fs)
    assert any(f.rule == "J5" and "not in the committed" in f.message
               for f in fs)


@pytest.mark.filterwarnings("ignore:Some donated buffers")
def test_injected_donation_miss_turns_gate_red():
    def inject(_srv, _audit):
        f = jax.jit(lambda a, b: (a + b).sum(), donate_argnums=(0,))
        f(jnp.ones((4,), F32), jnp.ones((4,), F32))
    rep = audit_config("dense", mutate=inject)
    manifest = json.loads(MANIFEST.read_text())
    manifest["configs"] = {"dense": manifest["configs"]["dense"]}
    fs = gate({"dense": rep}, manifest)
    assert any(f.rule == "J1" for f in fs)


# --------------------------------------------------------------- manifest
def _fake_report():
    entries = [
        TraceEntry("decode", "decode", "x.py", ("f32[2,8]",),
                   ("f32[2,8]",), "", (0,), None, False, "fake"),
        TraceEntry("prefill", "prefill", "x.py", ("i32[16]",),
                   ("f32[16,8]",), "n=16", (), None, False, "fake"),
    ]
    return {"fake": ConfigReport("fake", entries, [],
                                 {"decode": 1, "prefill": 1})}


def test_manifest_round_trip_is_green():
    reports = _fake_report()
    manifest = manifest_from_reports(reports, "0.0-test")
    assert compare_manifest(reports, manifest) == []
    assert gate(reports, manifest) == []


def test_unpinned_graph_is_drift():
    reports = _fake_report()
    manifest = manifest_from_reports(reports, "0.0-test")
    manifest["configs"]["fake"].pop()        # forget one pinned graph
    fs = compare_manifest(reports, manifest)
    assert len(fs) == 1 and fs[0].rule == "J5"
    assert "not in the committed" in fs[0].message


def test_stale_pin_is_drift():
    reports = _fake_report()
    manifest = manifest_from_reports(reports, "0.0-test")
    manifest["configs"]["fake"].append(
        {"fn": "ghost", "digest": "deadbeef0000", "in": [], "out": [],
         "static": "", "donate": []})
    fs = compare_manifest(reports, manifest)
    assert len(fs) == 1 and "stale pin" in fs[0].message


def test_missing_config_section_is_drift():
    reports = _fake_report()
    fs = compare_manifest(reports, {"configs": {}})
    assert any("no manifest section" in f.message for f in fs)


def test_waiver_requires_reason_and_suppresses():
    reports = _fake_report()
    manifest = manifest_from_reports(reports, "0.0-test")
    manifest["configs"]["fake"].pop()        # induce one J5 drift
    manifest["waivers"] = [{"rule": "J5", "config": "fake", "fn": "*"}]
    with pytest.raises(ValueError, match="reason"):
        gate(reports, manifest)
    manifest["waivers"][0]["reason"] = "transitional: re-pin next PR"
    assert gate(reports, manifest) == []
    assert load_waivers(manifest)[0]["reason"]


def test_committed_manifest_covers_every_config():
    manifest = json.loads(MANIFEST.read_text())
    assert set(manifest["configs"]) == set(ENGINE_SPECS)
    assert all(rows for rows in manifest["configs"].values())
    for w in load_waivers(manifest):        # committed waivers carry why
        assert w["reason"].strip()


# --------------------------------------------------------------- CLI gate
def run_cli(*argv, cwd=REPO):
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_audit.py"), *argv],
        cwd=cwd, capture_output=True, text=True)


def test_cli_list_configs():
    proc = run_cli("--list-configs")
    assert proc.returncode == 0
    for name in ENGINE_SPECS:
        assert name in proc.stdout


def test_cli_unknown_config_exits_2():
    proc = run_cli("--configs", "nope")
    assert proc.returncode == 2


def test_cli_green_then_red_on_corrupted_manifest(tmp_path):
    # green: one config vs the committed manifest (make trace-audit
    # scoped down); red: the same run vs a manifest missing one graph
    proc = run_cli("--configs", "dense", "--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    blob = json.loads(proc.stdout)
    assert blob["findings"] == [] and blob["n_graphs"] > 0

    manifest = json.loads(MANIFEST.read_text())
    manifest["configs"]["dense"].pop()
    bad = tmp_path / "manifest.json"
    bad.write_text(json.dumps(manifest))
    proc = run_cli("--configs", "dense", "--manifest", str(bad))
    assert proc.returncode == 1
    assert "not in the committed" in proc.stdout
