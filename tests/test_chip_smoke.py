"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phase functions, gate and served-path driver work at tiny sizes — so the
chip budget is never spent on a Python error (ISSUE 21).

The tier-1 tests drive the phases through the serial CPU backend (no
compile); the slow test drives the same phases through the fused Pallas
kernels in interpret mode, which traces the program the chip compiles.
"""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke
from tmtpu.crypto import ed25519_ref as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_cpu_before_any_heavy_work_and_names_the_platform():
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stdout and "refusing" in r.stdout
    # no lanes were generated, and there is no result line to parse
    assert "lanes generated" not in r.stdout
    last = r.stdout.strip().splitlines()[-1]
    assert not last.startswith("{")
    with pytest.raises(ValueError):
        json.loads(last)


def test_alone_in_a_directory_it_fails(tmp_path):
    """The script without the program: non-zero, no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, timeout=120, capture_output=True,
                       text=True)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_adversarial_lanes_are_rejections_under_the_spec_oracle():
    """Every ed25519 mutation kind is rejected by the Go-semantics
    oracle AND by the CPUBatchVerifier the smoke compares against."""
    items, bad = chip_smoke.make_lanes("ed25519", seed=1, n=24)
    assert len(bad) == 10
    want_mask, _tally = chip_smoke.reference(items)
    for i, (pk, msg, sig, _power) in enumerate(items):
        oracle = ref.verify(pk.bytes(), msg, sig)
        assert oracle == want_mask[i] == (i not in bad), i
    for curve in ("sr25519", "secp256k1"):
        items, bad = chip_smoke.make_lanes(curve, seed=1, n=24)
        mask, tally = chip_smoke.reference(items)
        assert mask == [i not in bad for i in range(24)]
        assert tally == sum(it[3] for i, it in enumerate(items)
                            if i not in bad)


def test_lanes_derive_from_the_seed():
    a, bad_a = chip_smoke.make_lanes("ed25519", seed=5, n=12)
    b, bad_b = chip_smoke.make_lanes("ed25519", seed=5, n=12)
    c, _ = chip_smoke.make_lanes("ed25519", seed=6, n=12)
    as_bytes = lambda items: [(pk.bytes(), m, s, p)  # noqa: E731
                              for pk, m, s, p in items]
    assert as_bytes(a) == as_bytes(b) and bad_a == bad_b
    assert as_bytes(a) != as_bytes(c)
    txs = chip_smoke.signed_txs(5, 8)
    assert txs == chip_smoke.signed_txs(5, 8)
    assert len({t[0] for t in txs}) == 8


@pytest.fixture
def closed_breakers():
    from tmtpu.libs import breaker as bk

    for name in chip_smoke.BREAKERS:
        bk.get(name).reset()
    yield
    for name in chip_smoke.BREAKERS:
        bk.get(name).reset()


def test_part_a_phases_at_tiny_size_and_the_gate(closed_breakers):
    """All of Part A's plumbing on the serial backend: flushes, the live
    round, verify_commit, the report lines — and a gate that passes a
    clean CPU run only when told not to expect a device."""
    fails = chip_smoke.Failures()
    meter = chip_smoke.Meter()
    cache: dict = {}
    out = chip_smoke.flush_phases(3, chip_smoke.flush_plan(48, 16), fails,
                                  meter, backend="cpu", lanes_cache=cache)
    assert set(out) == {"ed25519.tally.48", "ed25519.mask.48",
                        "sr25519.mask.16", "secp256k1.mask.16"}
    assert out["ed25519.tally.48"]["tallied"] is not None
    assert out["ed25519.tally.48"]["mask"] == out["ed25519.mask.48"]["mask"]
    # the mesh plan reuses the lanes; its narrower ed25519 mask flush is
    # the first lanes of the same set
    narrow = chip_smoke.flush_phases(
        3, chip_smoke.flush_plan(48, 16, mesh=True)[1:2], fails, meter,
        backend="cpu", lanes_cache=cache)
    assert narrow["ed25519.mask.16"]["mask"] == \
        out["ed25519.mask.48"]["mask"][:16]
    r = chip_smoke.live_round_phase(40, 3, fails, meter, backend="cpu")
    # the live validator holds 40 of the set's 79 in power: its own
    # precommit and 13 more pass 2/3, however many the drain then held
    assert r["validators"] == 40 and r["precommits_in_commit"] >= 14
    chip_smoke.gate(fails, meter, expect_device=False)
    assert fails == []
    # the same observations fail the real gate: nothing ran on a TPU,
    # nothing ran the Pallas kernel
    strict = chip_smoke.Failures()
    chip_smoke.gate(strict, meter, expect_device=True)
    assert any("platform 'cpu'" in f for f in strict)
    assert any("no dispatch ran impl=pallas" in f for f in strict)


def test_flush_phase_reports_a_wrong_mask_and_tally():
    items, bad = chip_smoke.make_lanes("ed25519", seed=2, n=24)
    mask, tally = chip_smoke.reference(items)
    lie = list(mask)
    lie[3] = not lie[3]
    fails = chip_smoke.Failures()
    chip_smoke.flush_phase("ed25519.tally", items, bad, (lie, tally + 1),
                           True, fails, chip_smoke.Meter(),
                           backend="cpu", reps=1)
    assert any("mask differs" in f and "[3]" in f for f in fails)
    assert any("device tally" in f for f in fails)


def _device_meter():
    meter = chip_smoke.Meter()
    meter.device_series = {"curve=ed25519,backend=tpu,impl=pallas":
                           {"count": 4, "sum": 0.2}}
    return meter


@pytest.mark.parametrize("reason", chip_smoke.FORBIDDEN_FALLBACKS)
def test_gate_fails_on_a_fired_safety_ladder(closed_breakers, reason):
    """The gate judges what happened since its meter was made: policy
    fallbacks pass, a device-error / deadline / breaker-open /
    probe-failed fallback does not."""
    from tmtpu.libs import metrics as _m

    meter = _device_meter()
    _m.crypto_cpu_fallback.inc(3, curve="ed25519", reason="small-batch")
    clean = chip_smoke.Failures()
    chip_smoke.gate(clean, meter)
    assert clean == []
    _m.crypto_cpu_fallback.inc(10240, curve="ed25519", reason=reason)
    fired = chip_smoke.Failures()
    chip_smoke.gate(fired, meter)
    assert len(fired) == 1 and reason in fired[0] and "+10240" in fired[0]
    # a run that starts now has a clean slate again
    later = chip_smoke.Failures()
    chip_smoke.gate(later, _device_meter())
    assert later == []


def test_gate_fails_on_xla_rung_breaker_failure_and_mesh_fallback(
        closed_breakers):
    from tmtpu.libs import breaker as bk
    from tmtpu.libs import metrics as _m

    meter = _device_meter()
    meter.device_series["curve=sr25519,backend=tpu,impl=xla"] = {
        "count": 1, "sum": 0.4}
    bk.get("pallas.sr25519").record_failure(RuntimeError("mosaic said no"))
    _m.crypto_mesh_fallback_total.inc(7, curve="ed25519",
                                      reason="device-error")
    _m.crypto_batch_deadline_exceeded.inc(curve="secp256k1")
    fails = chip_smoke.Failures()
    chip_smoke.gate(fails, meter)
    assert any("left the Pallas kernel" in f and "sr25519" in f
               for f in fails)
    assert any("breaker_failures" in f and "pallas.sr25519" in f
               for f in fails)
    assert any("mesh_fallback" in f for f in fails)
    assert any("deadline_exceeded" in f for f in fails)
    bk.get("pallas.sr25519").trip_permanent("Mosaic rejected the kernel")
    fails = chip_smoke.Failures()
    chip_smoke.gate(fails, _device_meter())
    assert any("breaker pallas.sr25519 is open" in f for f in fails)


def test_part_b_served_path_at_tiny_size_on_the_cpu_engine():
    """sidecar + JAX-poisoned node + RPC client, end to end, with the
    daemon on the serial engine: commits exactly once, reads back, zero
    client fallbacks, both children exit 0 on SIGTERM, and this parent
    never imported jax to do it (it already had, under pytest — the
    node child is the process that must not)."""
    fails = chip_smoke.Failures()
    chip_smoke.part_b(4, fails, n_txs=96, sidecar_backend="cpu",
                      expect_device=False, start_timeout=120.0)
    assert fails == []


@pytest.mark.slow
def test_part_a_flushes_through_the_interpreted_pallas_kernels(monkeypatch):
    """The device path for real, as far as a CPU can take it: every
    curve's flush through ``new_batch_verifier("tpu")`` with the fused
    kernels in interpret mode, masks equal to the reference."""
    from tmtpu.crypto import batch as crypto_batch

    monkeypatch.setenv("TMTPU_TPU_IMPL", "pallas")
    # interpret-mode compiles outlast the production deadline on a CPU
    monkeypatch.setenv("TMTPU_TPU_BATCH_DEADLINE", "0")
    monkeypatch.setenv("TMTPU_MESH_DEVICES", "1")
    monkeypatch.setattr(crypto_batch, "_default_backend", "tpu")
    fails = chip_smoke.Failures()
    meter = chip_smoke.Meter(chip_smoke.CompileWatch())
    chip_smoke.flush_phases(3, chip_smoke.flush_plan(24, 16), fails, meter)
    chip_smoke.gate(fails, meter, expect_device=False)
    assert fails == []
    assert all("impl=pallas" in k for k in meter.device_series)
    assert {k.split(",")[0] for k in meter.device_series} == {
        "curve=ed25519", "curve=sr25519", "curve=secp256k1"}
