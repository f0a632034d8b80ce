"""Dry-run smoke: the case builder lowers+compiles on a small in-process
mesh (subprocess so the forced host-device count never leaks into other
tests)."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro import compat
from repro.launch import cases

out = {}
mesh = compat.make_mesh((2, 4), ("data", "model"))
for arch, shape in [("internlm2-1.8b", "decode_32k"),
                    ("xlstm-350m", "long_500k"),
                    ("qwen2-vl-2b", "prefill_32k")]:
    case = cases.input_specs(arch, shape, mesh)
    compiled = case.lower(mesh).compile()
    ma = compiled.memory_analysis()
    out[f"{arch}:{shape}"] = int(ma.temp_size_in_bytes)

# federated forest protocol on a (trees, parties) mesh
fmesh = compat.make_mesh((2, 4), ("trees", "parties"))
fn, args, _ = cases.forest_case("ff_train", fmesh)
c = jax.jit(fn).lower(*args).compile()
out["ff_train"] = int(c.memory_analysis().temp_size_in_bytes)
print(json.dumps(out))
"""


@pytest.mark.slow
def test_cases_lower_on_small_mesh():
    env = dict(os.environ, PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1500,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert len(out) == 4
    for k, v in out.items():
        assert v > 0, (k, v)


def test_skip_table_is_principled():
    from repro.launch import cases
    assert ("whisper-large-v3", "long_500k") in cases.SKIPS
    with pytest.raises(cases.Skip):
        cases.arch_for_shape("whisper-large-v3", cases.SHAPES["long_500k"])


def test_swa_variant_applied_for_long_context():
    from repro.launch import cases
    cfg = cases.arch_for_shape("qwen3-32b", cases.SHAPES["long_500k"])
    assert cfg.sliding_window == cases.SWA_WINDOW
    cfg = cases.arch_for_shape("xlstm-350m", cases.SHAPES["long_500k"])
    assert cfg.sliding_window is None  # natively sub-quadratic
    cfg = cases.arch_for_shape("qwen3-32b", cases.SHAPES["decode_32k"])
    assert cfg.sliding_window is None  # full attention below 500k
