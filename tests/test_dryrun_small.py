"""Dry-run machinery on a small forced-device mesh (subprocess): proves
the lower/compile/analyze path works end-to-end for each step kind
without the 512-device production mesh."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "{src}")
import json
import jax
from repro.configs import get_arch
from repro.launch import dryrun  # safe: we already set XLA_FLAGS
from repro.launch import shapes as shapes_mod
from repro.sharding import api as shapi
import dataclasses

cfg = get_arch("{arch}").reduced()
# shrink the shape cell for CPU compile
shapes_mod.SHAPES = dict(shapes_mod.SHAPES)
shapes_mod.SHAPES["tiny"] = shapes_mod.ShapeCell("tiny", "{kind}", 64, 8)
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(model=2)   # (4, 2) data x model, Auto axes
plan = shapi.tp_plan(data_axes=("data",), model_axis="model", fsdp=False)
compiled, kind, (tl, tc) = dryrun._lower_and_compile(
    cfg, "tiny", mesh, plan)
m = dryrun._measure(compiled)
mem = compiled.memory_analysis()
assert m["flops"] > 0
assert kind == "{kind}"
print("OK", json.dumps({{"flops": m["flops"], "coll": m["coll"],
                        "temp": int(mem.temp_size_in_bytes)}}))
"""


@pytest.mark.parametrize("arch,kind", [
    ("olmo-1b", "train"),
    ("llama3-405b", "prefill"),
    ("falcon-mamba-7b", "decode"),
    ("zamba2-1.2b", "train"),
    ("whisper-medium", "prefill"),
    ("qwen2-vl-2b", "decode"),
    ("arctic-480b", "train"),
])
def test_dryrun_cell_small_mesh(arch, kind):
    src = str(Path(__file__).resolve().parent.parent / "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(src=src, arch=arch,
                                             kind=kind)],
        capture_output=True, text=True, timeout=600)
    assert "OK" in out.stdout, (out.stdout[-800:], out.stderr[-3000:])
    payload = json.loads(out.stdout.split("OK", 1)[1])
    assert payload["flops"] > 0


def test_collective_bytes_parser():
    from repro.utils.hlo import collective_bytes
    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(bf16[1,128]{1,0} %x), dims={0}
  %ar.1 = f32[256]{0} all-reduce(f32[256]{0} %y), to_apply=%sum
  %rs = f32[32]{0} reduce-scatter(f32[256]{0} %z), dimensions={0}
  %cp = f32[64]{0} collective-permute(f32[64]{0} %w)
  %a2a = bf16[4,64]{1,0} all-to-all(bf16[4,64]{1,0} %v), dimensions={0}
  %ard = f32[256]{0} all-reduce-done(f32[256]{0} %ars)
  %dot = f32[8,8]{1,0} dot(f32[8,8] %a, f32[8,8] %b)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 8 * 128 * 2          # larger buffer
    assert out["all-reduce"] == 2 * 256 * 4          # 2x ring multiplier
    assert out["reduce-scatter"] == 256 * 4
    assert out["collective-permute"] == 64 * 4
    assert out["all-to-all"] == 4 * 64 * 2
    assert out["count"] == 5
