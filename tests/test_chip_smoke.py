"""chip_smoke.py: its refusals, and each phase rehearsed at toy size.

The script itself runs only on a TPU; here its phase functions run on the
CPU at toy sizes (reduced models, few clients and steps), with the same
checks they make on the chip. The four-chip phases run in a subprocess on
four forced host devices, because XLA fixes the device count when the
backend starts.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
SMOKE = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
        yield chip_smoke
    finally:
        sys.path.remove(str(ROOT))


def _cpu_env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def _run(args, cwd, env, timeout=300):
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_smoke_refuses_without_chip_or_checkout(tmp_path, where):
    """No TPU, or no repo beside the script: non-zero, and no ok line."""
    if where == "alone":
        shutil.copy(SMOKE, tmp_path / SMOKE.name)
        proc = _run([SMOKE.name], tmp_path, _cpu_env())
    else:
        proc = _run([str(SMOKE)], ROOT, _cpu_env())
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_train_phase_toy(smoke):
    with smoke.CompileMeter() as meter:
        out = smoke.train_phase(meter, reduced=True, image_size=16,
                                num_train=2000, num_clients=10,
                                global_batch=32, steps=20)
    assert out["steps"] == 20 and len(out["losses"]) == 20
    assert out["loss_last5_mean"] < out["loss_first"]
    assert out["compile_s"] > 0
    json.dumps(out)                                 # one printable line


def test_serve_phase_toy_is_exact(smoke):
    with smoke.CompileMeter() as meter:
        out = smoke.serve_phase(meter, reduced=True, prompt_lens=(8, 24),
                                max_new=4, num_requests=5, margin=0.0)
    assert out["verified"] == 5 and out["excused"] == []
    assert out["warm_compile_s"] == 0.0             # the warm pass reuses
    json.dumps(out)


_FOUR = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
import chip_smoke as cs
from repro import sharding as shard_lib
from repro.api import ModelSpec, build_model

with cs.CompileMeter() as meter:
    full = cs.four_chip_full_depth(meter, reduced=True, seq_len=32,
                                   global_batch=16, steps=2)
    agree = cs.four_chip_agreement(meter, reduced=True, seq_len=32,
                                   global_batch=16)
# the whole-on-one-device check must see replicated parameters
model = build_model(ModelSpec(arch="granite-3-2b", reduced=True))
mesh = jax.make_mesh((4, 1), ("data", "model"))
rep = jax.device_put(model.init(jax.random.PRNGKey(0)),
                     shard_lib.replicated(mesh))
whole = cs._whole_on_one_device(model, rep, mesh, "fsdp")
print(json.dumps({{"full": full, "agree": agree, "whole": whole}}))
"""


def test_four_chip_phases_on_host_devices():
    code = _FOUR.format(root=str(ROOT), src=str(ROOT / "src"))
    proc = _run(["-c", code], ROOT, _cpu_env(
        XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    full, agree = out["full"], out["agree"]
    assert full["device"]["count"] == 4 and len(full["losses"]) == 2
    assert full["sharding_fallbacks"] == []
    assert agree["params_beyond_tol_frac"] <= 0.02
    assert abs(agree["loss_4x1"] - agree["loss_1x1"]) \
        <= 2.0 ** -7 * abs(agree["loss_1x1"])
    # every server leaf carries an fsdp-sharded embed axis
    assert any(p.startswith("server") for p in out["whole"])
