"""chip_smoke.py's phases at reduced size on the CPU (Pallas kernels in
interpret mode), its four-replica fleet on four virtual CPU devices, and
its refusal to run without a TPU."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402

EDGE = get_config("yi-6b").reduced()
CLOUD = get_config("yi-9b").reduced(n_layers=3)


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def test_kernels_phase_matches_oracles():
    errs = cs.phase_kernels(prefill_lens=(8, 128), cache_len=256, batch=2)
    assert set(errs) == {"flash_s8", "flash_s128", "decode", "paged"}
    assert max(errs.values()) <= cs.KERNEL_TOL


def test_engine_phase_serves_every_request():
    out = cs.phase_engine(EDGE, max_seq=128,
                          prompt_lens=(3, 5, 7, 9, 12, 17, 20, 30),
                          max_new=4)
    assert out["steps_per_s"] > 0


def test_parity_phase_within_limits():
    out = cs.phase_parity(get_config("yi-6b").reduced())
    for rel_l2, max_abs in out.values():
        assert rel_l2 <= cs.PARITY_REL_L2 and max_abs <= cs.PARITY_MAX_ABS


def test_fleet_phase_serves_every_request():
    result, srv = cs.phase_fleet(EDGE, CLOUD)
    assert len(result) == 12 and len(srv.engines) == 3
    assert all(len(tokens) == cs.FLEET_MAX_NEW for _, tokens in result)
    assert {server for server, _ in result} == {0, 1, 2}


def test_fleet4_one_device_per_replica_matches_device0():
    """The --chips 4 phase on four virtual CPU devices: every engine's
    arrays on its own device, same servers and tokens as all on one."""
    script = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import jax
        import chip_smoke as cs
        from repro.configs import get_config
        edge = get_config("yi-6b").reduced()
        cloud = get_config("yi-9b").reduced(n_layers=3)
        out = cs.phase_fleet4(edge, cloud, jax.devices()[:4])
        print("FLEET4 OK", len(out))
    """)
    r = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600, cwd=ROOT, env=_cpu_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "FLEET4 OK 12" in r.stdout
    assert "devices=0,1,2,3" in r.stdout


def test_chip_smoke_without_tpu_exits_before_any_phase(tmp_path):
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       cwd=tmp_path, env=_cpu_env())
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    for phase in ("kernels:", "engine:", "parity:", "fleet", '"ok"'):
        assert phase not in r.stdout
