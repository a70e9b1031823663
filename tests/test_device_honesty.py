"""The device is selected honestly, one process owns a chip, and the
compile cache can be placed from outside (ISSUE 21).

- ``auto`` on a CPU host is the CPU backend, finally and without breaker
  noise; a probe that raises is retried, one that answers is not;
- an explicit ``tpu`` at ``tmtpu start`` / ``tmtpu sidecar`` exits
  non-zero naming the platform unless ``JAX_PLATFORMS=cpu`` asked for
  the emulation; ``bench.py`` refuses the emulation too;
- a ``crypto_backend = sidecar`` node falls back to the serial CPU
  verifier and never imports or probes JAX;
- ``setup_compile_cache`` in its three cases;
- the start-up report and the sidecar's ``backend_name`` say what JAX
  found; the warm-up ladders cover the production bucket policy.
"""

import os
import subprocess
import sys

import pytest

from tmtpu.crypto import batch as crypto_batch
from tmtpu.crypto import ed25519 as ed
from tmtpu.libs import breaker as bk
from tmtpu.libs import metrics as _m
from tmtpu.tpu import compat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fallbacks(reason: str) -> float:
    return sum(v for k, v in
               _m.crypto_cpu_fallback.summary_series().items()
               if f"reason={reason}" in k)


@pytest.fixture
def fresh_probe(monkeypatch):
    monkeypatch.setattr(crypto_batch, "_tpu_usable", None)
    br = bk.get(crypto_batch.BREAKER_NAME)
    br.reset()
    yield br
    br.reset()


# --- auto ------------------------------------------------------------------


def test_auto_on_a_cpu_host_is_the_cpu_backend(fresh_probe):
    failures0 = _m.crypto_breaker_failures.summary_series().get(
        "breaker=crypto.tpu", 0)
    probe_failed0 = _fallbacks("probe-failed")
    bv = crypto_batch.new_batch_verifier("auto")
    assert type(bv) is crypto_batch.CPUBatchVerifier
    # the answer is final: no re-probe, no breaker failure, no fallback
    # counted against a device that was never there
    assert crypto_batch._tpu_usable is False
    attempts = sum(_m.crypto_device_probe_attempts
                   .summary_series().values())
    for _ in range(5):
        assert type(crypto_batch.new_batch_verifier("auto")) is \
            crypto_batch.CPUBatchVerifier
    assert sum(_m.crypto_device_probe_attempts
               .summary_series().values()) == attempts
    assert fresh_probe.state == bk.CLOSED
    assert _m.crypto_breaker_failures.summary_series().get(
        "breaker=crypto.tpu", 0) == failures0
    assert _fallbacks("probe-failed") == probe_failed0
    assert _m.crypto_tpu_backend_up.summary_series()[""] == 0.0


def test_auto_is_the_device_backend_only_on_platform_tpu(fresh_probe,
                                                         monkeypatch):
    monkeypatch.setattr(compat, "device_platform", lambda: "tpu")
    assert type(crypto_batch.new_batch_verifier("auto")) is \
        crypto_batch.TPUBatchVerifier
    assert _m.crypto_tpu_backend_up.summary_series()[""] == 1.0
    # another accelerator is not what the kernels were written for
    monkeypatch.setattr(crypto_batch, "_tpu_usable", None)
    monkeypatch.setattr(compat, "device_platform", lambda: "gpu")
    assert type(crypto_batch.new_batch_verifier("auto")) is \
        crypto_batch.CPUBatchVerifier


def test_a_probe_that_raises_is_retried_and_counted(fresh_probe,
                                                    monkeypatch):
    def boom():
        raise RuntimeError("backend init failed")

    monkeypatch.setattr(compat, "device_platform", boom)
    before = _fallbacks("probe-failed")
    assert not crypto_batch._tpu_available()
    assert crypto_batch._tpu_usable is None      # not an answer
    assert _fallbacks("probe-failed") == before + 1
    # the runtime comes back: the next probe finds the chip
    monkeypatch.setattr(compat, "device_platform", lambda: "tpu")
    assert crypto_batch._tpu_available()


def test_use_pallas_kernel_and_interpret_default_surface_jax_failures(
        monkeypatch):
    import jax

    from tmtpu.tpu import dispatch
    from tmtpu.tpu import kernel as tk

    def boom():
        raise RuntimeError("no backend")

    monkeypatch.delenv("TMTPU_TPU_IMPL", raising=False)
    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError):
        dispatch.use_pallas_kernel()
    with pytest.raises(RuntimeError):
        tk._default_interpret()


# --- explicit tpu ------------------------------------------------------------


def test_require_tpu_names_the_platform_it_found(monkeypatch):
    monkeypatch.setattr(compat, "device_info", lambda: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setenv("JAX_PLATFORMS", "")
    with pytest.raises(SystemExit) as e:
        compat.require_tpu("tmtpu start")
    assert "tmtpu start" in str(e.value) and "'cpu'" in str(e.value)
    # CPU emulation asked for: the launch passes, a measurement does not
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert compat.require_tpu("tmtpu start")["platform"] == "cpu"
    with pytest.raises(SystemExit) as e:
        compat.require_tpu("bench.py", allow_emulation=False)
    assert "'cpu'" in str(e.value)
    monkeypatch.setattr(compat, "device_info", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert compat.require_tpu("bench.py", allow_emulation=False)[
        "kind"] == "TPU v5 lite"


def _run_cli(args, env_extra, timeout=120):
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("JAX_PLATFORMS", None)
    env.update(env_extra)
    return subprocess.run(
        [sys.executable] + args, cwd=REPO, env=env, timeout=timeout,
        capture_output=True, text=True)


def test_cmd_sidecar_explicit_tpu_without_a_tpu_exits_nonzero(tmp_path):
    r = _run_cli(["-m", "tmtpu.cmd", "sidecar", "--home", str(tmp_path),
                  "--backend", "tpu", "--addr",
                  f"unix://{tmp_path}/s.sock"], {})
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "sidecar" in r.stderr
    assert "Sidecar listening" not in r.stdout


def test_cmd_start_explicit_tpu_without_a_tpu_exits_nonzero(tmp_path):
    home = str(tmp_path / "home")
    assert _run_cli(["-m", "tmtpu.cmd", "init", "--home", home],
                    {}).returncode == 0
    r = _run_cli(["-m", "tmtpu.cmd", "start", "--home", home,
                  "--crypto-backend", "tpu"], {})
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "node" in r.stderr
    assert "Node started" not in r.stdout


def test_bench_refuses_cpu_and_names_it():
    """``JAX_PLATFORMS=cpu python bench.py``: non-zero, names the
    platform, prints no metric line."""
    r = _run_cli(["bench.py"], {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "bench.py" in r.stderr
    assert "metric" not in r.stdout


# --- one process per chip ----------------------------------------------------


def test_sidecar_client_fallback_never_touches_jax(monkeypatch):
    """A sidecar node's local fallback is the serial CPU verifier: it
    must not probe for a device the daemon owns."""
    def forbidden(*_a, **_k):
        raise AssertionError("a sidecar node probed for a JAX device")

    monkeypatch.setattr(crypto_batch, "_tpu_available", forbidden)
    monkeypatch.setattr(compat, "device_info", forbidden)
    monkeypatch.setattr(crypto_batch.TPUBatchVerifier, "_verify_pending",
                        forbidden)
    monkeypatch.setitem(crypto_batch._sidecar_state, "addr", "")
    monkeypatch.setitem(crypto_batch._sidecar_state, "home", "")
    monkeypatch.delenv("TMTPU_SIDECAR_ADDR", raising=False)
    crypto_batch.reset_sidecar_client()
    priv = ed.gen_priv_key_from_secret(b"sidecar-fallback")
    before = sum(_m.sidecar_client_fallback.summary_series().values())
    bv = crypto_batch.new_batch_verifier("sidecar")
    for i in range(12):
        msg = b"fallback-%d" % i
        sig = priv.sign(msg)
        bv.add(priv.pub_key(), msg, sig if i != 5 else sig[:-1] + b"\0",
               power=2)
    all_ok, mask, tallied = bv.verify_tally()
    assert mask == [i != 5 for i in range(12)] and not all_ok
    assert tallied == 22
    assert sum(_m.sidecar_client_fallback.summary_series().values()) \
        == before + 12


def test_a_sidecar_node_process_cannot_import_jax(tmp_path):
    """Node.__init__ + verifier construction + a fallback flush in a
    process where importing jax raises: the path chip_smoke.py Part B
    runs with a real daemon."""
    poison = tmp_path / "nojax" / "jax"
    poison.mkdir(parents=True)
    (poison / "__init__.py").write_text(
        'raise ImportError("jax imported in a sidecar node")\n')
    code = (
        "from tmtpu.config.config import Config\n"
        "from tmtpu.crypto import batch as cb, ed25519 as ed\n"
        "from tmtpu.node.node import Node\n"
        "from tmtpu.privval.file_pv import FilePV\n"
        "from tmtpu.types.genesis import GenesisDoc, GenesisValidator\n"
        "import os, sys, time\n"
        "cfg = Config.test_config(); cfg.base.home = sys.argv[1]\n"
        "cfg.base.crypto_backend = 'sidecar'; cfg.rpc.laddr = ''\n"
        "os.makedirs(cfg.rooted('config')); os.makedirs(cfg.rooted('data'))\n"
        "pv = FilePV.load_or_generate(\n"
        "    cfg.rooted(cfg.base.priv_validator_key_file),\n"
        "    cfg.rooted(cfg.base.priv_validator_state_file))\n"
        "GenesisDoc(chain_id='nojax', genesis_time=time.time_ns(),\n"
        "    validators=[GenesisValidator(pv.get_pub_key(), 10)]\n"
        "    ).save_as(cfg.genesis_path)\n"
        "node = Node(cfg)\n"
        "assert node.verify_device['backend'] == 'sidecar'\n"
        "k = ed.gen_priv_key_from_secret(b'x')\n"
        "bv = cb.new_batch_verifier()\n"
        "for i in range(16): bv.add(k.pub_key(), b'm%d' % i,"
        " k.sign(b'm%d' % i))\n"
        "assert bv.verify()[0]\n"
        "assert 'jax' not in sys.modules\n"
        "print('NOJAX-OK')\n")
    r = _run_cli(["-c", code, str(tmp_path / "home")],
                 {"PYTHONPATH": f"{poison.parent}{os.pathsep}{REPO}",
                  "TMTPU_SIDECAR_ADDR": f"unix://{tmp_path}/absent.sock"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "NOJAX-OK" in r.stdout


# --- the compile cache -------------------------------------------------------


def _cache_probe(env_extra):
    code = ("from tmtpu.tpu import compat\n"
            "d = compat.setup_compile_cache()\n"
            "import jax\n"
            "print(repr((d, jax.config.jax_compilation_cache_dir)))\n")
    r = _run_cli(["-c", code], dict(env_extra, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    return eval(r.stdout.strip().splitlines()[-1])


def test_cache_dir_from_the_environment_sets_none_in_code(tmp_path,
                                                          monkeypatch):
    # in this process: with the variable set the function must not touch
    # jax.config at all (grep finds no update reached at run time)
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def forbidden(*a, **k):
        raise AssertionError(f"jax.config.update{a} with the variable set")

    monkeypatch.setattr(jax.config, "update", forbidden)
    assert compat.setup_compile_cache() == str(tmp_path)
    # in a fresh process JAX itself picks the directory up
    returned, configured = _cache_probe(
        {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert returned == configured == str(tmp_path)


def test_cache_dir_defaults_to_the_checkout():
    returned, configured = _cache_probe({"JAX_COMPILATION_CACHE_DIR": ""})
    assert returned == configured == os.path.join(REPO, ".jax_cache")


def test_cache_dir_is_never_temporary_or_per_process():
    import tempfile

    d = compat.DEFAULT_CACHE_DIR
    assert d == os.path.join(REPO, ".jax_cache")
    assert not d.startswith(tempfile.gettempdir())
    assert str(os.getpid()) not in d
    src = open(os.path.join(REPO, "tmtpu", "tpu", "compat.py")).read()
    for banned in ("tempfile", "getpid", "time.time", "TMTPU_COMPILE_CACHE",
                   "TMTPU_NO_COMPILE_CACHE"):
        assert banned not in src


# --- start-up report, sidecar name, warm-up ----------------------------------


def test_start_backend_reports_what_it_found(monkeypatch):
    info = crypto_batch.start_backend("cpu", "test")
    assert info["backend"] == "cpu" and info["platform"] == "none"
    assert info["native"] in (True, False) and info["cache_dir"] == ""
    info = crypto_batch.start_backend("sidecar", "test")
    assert info["backend"] == "sidecar" and info["count"] == 0
    # explicit tpu under the tests' JAX_PLATFORMS=cpu emulation
    info = crypto_batch.start_backend("tpu", "test")
    assert info["backend"] == "tpu" and info["platform"] == "cpu"
    assert info["count"] >= 1 and info["cache_dir"]
    assert _m.crypto_tpu_backend_up.summary_series()[""] == 0.0
    # auto resolves to what the process will really verify on
    monkeypatch.setattr(crypto_batch, "_tpu_usable", None)
    assert crypto_batch.start_backend("auto", "test")["backend"] == "cpu"


def test_sidecar_backend_name_is_the_jax_platform(monkeypatch, tmp_path):
    from tmtpu.sidecar.server import SidecarServer

    srv = SidecarServer(f"unix://{tmp_path}/a.sock", backend="cpu")
    assert srv.backend_name() == "cpu" and srv.snapshot()["device"] == {}
    srv = SidecarServer(f"unix://{tmp_path}/b.sock", backend="tpu")
    # the device graph on XLA:CPU is not a TPU and does not say it is
    assert srv.backend_name() == "xla:cpu"
    assert srv.snapshot()["device"]["platform"] == "cpu"
    with monkeypatch.context() as mp:
        mp.setattr(compat, "device_platform", lambda: "tpu")
        assert srv.backend_name() == "tpu"
    # auto on this CPU host resolves to the serial engine
    monkeypatch.setattr(crypto_batch, "_tpu_usable", None)
    srv = SidecarServer(f"unix://{tmp_path}/c.sock", backend="auto")
    assert srv.backend_name() == "cpu"


def test_warm_sizes_cover_every_production_bucket():
    from tmtpu.tpu import dispatch

    for top in (8, 100, 512, 2000):
        sizes = crypto_batch._warm_sizes(top)
        assert sizes[0] == crypto_batch._TPU_MIN_BATCH and sizes[-1] == top
        warmed = {dispatch._pad_to_bucket(n) for n in sizes}
        assert warmed == {dispatch._pad_to_bucket(n)
                          for n in range(crypto_batch._TPU_MIN_BATCH,
                                         top + 1)}
    assert crypto_batch._warm_sizes(crypto_batch._TPU_MIN_BATCH - 1) == []


def test_warm_validator_set_flushes_below_the_sigcache(monkeypatch):
    from tmtpu.types.validator import Validator, ValidatorSet

    flushed = []

    def fake_pending(self, items, tally):
        flushed.append((items[0][0].type_value(), len(items), tally))
        return [True] * len(items), len(items)

    monkeypatch.setattr(crypto_batch.TPUBatchVerifier, "_verify_pending",
                        fake_pending)
    vals = ValidatorSet([
        Validator(ed.gen_priv_key_from_secret(b"w%d" % i).pub_key(), 1)
        for i in range(70)])
    out = crypto_batch.warm_validator_set(vals)
    # a set under a drain's worth: every vote flush is pinned to the set
    assert flushed == [("ed25519", 70, True)]
    assert [(c, n, t) for c, n, t, _s in out] == flushed
    # a set too small to ever reach the device warms nothing
    flushed.clear()
    assert crypto_batch.warm_validator_set(
        ValidatorSet(vals.validators[:4])) == [] and not flushed
    # the daemon warms both steps up to its dispatch cap ...
    crypto_batch.warm_daemon(100)
    assert flushed == [("ed25519", n, t) for t in (False, True)
                       for n in (8, 65, 100)]
    # ... or its own bound, never the 40,960-lane default cap
    flushed.clear()
    crypto_batch.warm_daemon(40960)
    assert max(n for _c, n, _t in flushed) == 2048


def test_warm_validator_set_reaches_the_whole_set(monkeypatch):
    """A vote flush is pinned to a drain's worth or to the whole set
    (vote_flush_lanes), and verify_commit flushes a whole commit: those
    two shapes are warmed and no other (the first chip run compiled the
    4,096 bucket inside the live 10k round; a ladder of every bucket then
    cost 11 compiles, 190 s)."""
    from tmtpu.tpu import dispatch

    flushed = []
    monkeypatch.setattr(
        crypto_batch.TPUBatchVerifier, "_verify_pending",
        lambda self, items, tally: (flushed.append(len(items))
                                    or ([True] * len(items), 0)))

    class FakeSet:
        validators = [type("V", (), {"pub_key": ed.gen_priv_key_from_secret(
            b"one").pub_key()})()] * 10_000

    crypto_batch.warm_validator_set(FakeSet)
    assert flushed == [crypto_batch.DRAIN_LANES, 10_000]
    assert [dispatch._pad_to_bucket(n) for n in flushed] == [1024, 10240]
    # whatever a flush holds, its pin is one of the two
    assert {dispatch._pad_to_bucket(crypto_batch.vote_flush_lanes(10_000, n))
            for n in range(1, 10_001)} == {1024, 10240}
