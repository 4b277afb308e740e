"""chip_smoke.py rehearsed on the CPU at a tiny width: its phases run
the serving CLI's functions end to end, and its guards refuse to report
a result anywhere but on a TPU."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY_A = SimpleNamespace(requests=4, prompt_len=24, gen=8, batch=2,
                         page_size=4, shared_prefix=0)
TINY_B = SimpleNamespace(requests=4, prompt_len=24, gen=8, batch=2,
                         page_size=4, shared_prefix=12)


@pytest.fixture(scope="module")
def tiny():
    from repro.configs import get_tiny_config
    cfg = get_tiny_config(chip_smoke.ARCH)
    params, _ = chip_smoke.replicated_params(cfg, jax.devices()[:1])
    return cfg, params


@pytest.mark.parametrize("phase", ["a", "b"])
def test_phase_runs_tiny(tiny, phase, capsys):
    cfg, params = tiny
    if phase == "a":
        chip_smoke.phase_a(cfg, params, size=TINY_A)
    else:
        chip_smoke.phase_b(cfg, params, size=TINY_B)
    out = capsys.readouterr().out
    assert f"[smoke] phase {phase.upper()}: served 32 tokens" in out


def test_compare_logits_rejects_wrong_and_nonfinite():
    import numpy as np
    ref = np.array([[1.0, -2.0, 4.0]], np.float32)
    chip_smoke.compare_logits("same", ref + 1e-3, ref)
    with pytest.raises(RuntimeError, match="differ"):
        chip_smoke.compare_logits("off", ref + 0.5, ref)
    with pytest.raises(RuntimeError, match="non-finite"):
        chip_smoke.compare_logits("nan", ref * np.nan, ref)


def test_four_chip_phase_on_virtual_devices():
    """The --four-chips path at tiny width on four virtual CPU devices:
    pools striped over all four, logits within tolerance of 1x1."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    code = textwrap.dedent(f"""
        import sys
        from types import SimpleNamespace
        sys.path.insert(0, {ROOT!r})
        import jax
        import chip_smoke
        from repro.configs import get_tiny_config
        cfg = get_tiny_config(chip_smoke.ARCH)
        devs = jax.devices()[:4]
        params, rep = chip_smoke.replicated_params(cfg, devs)
        chip_smoke.four_chips(cfg, params, rep, devs, size=SimpleNamespace(
            requests=4, prompt_len=24, gen=8, batch=2, page_size=4,
            shared_prefix=0))
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "1x4 striped vs 1x1" in r.stdout


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_refuses_cpu_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert _no_result(r.stdout)


def test_refuses_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert _no_result(r.stdout)
