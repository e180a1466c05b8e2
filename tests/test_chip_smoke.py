"""chip_smoke.py off the chip: it refuses to report without a TPU, and its
phases run end to end at the reduced config on the CPU (the rehearsal for
the chip run, which takes the same code at published widths)."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, cwd, extra_env=None, timeout=300):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=timeout,
    )


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_refuses_without_a_tpu(tmp_path, where):
    """No TPU, or no repo beside the script: non-zero exit and no result."""
    script = SCRIPT
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    out = _run([script], cwd=str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_serve_phase_matches_sequential_decode_at_reduced_size():
    from repro.configs import get_reduced

    cs = _load()
    r = cs.serve_phase(get_reduced("tinyllama-1.1b"))
    assert r["tokens_total"] > 0 and r["near_ties_ok"], r
    assert r["preemptions"] == 0 and r["peak_pages"] <= r["pages_total"]


def test_train_phase_on_four_virtual_devices():
    code = f"""
    import importlib.util, json
    from repro.configs import get_reduced
    spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    r = cs.train_phase(get_reduced("tinyllama-1.1b"), cut_layers=2, seq_len=32, batch=4)
    print(json.dumps(r))
    """
    out = _run(
        ["-c", textwrap.dedent(code)], cwd=REPO,
        extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                   "PYTHONPATH": os.path.join(REPO, "src")},
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["ok"], r
    assert len(r["full_losses"]) == 3
