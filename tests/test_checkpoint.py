"""Checkpoint substrate: roundtrip, commit marker, retention, async,
elastic restore onto different shardings (subprocess w/ 8 devices)."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import io as ckpt


def make_tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32),
            "b": {"c": jnp.asarray(rng.integers(0, 5, (4,)), jnp.int32),
                  "d": jnp.asarray(rng.standard_normal(()), jnp.float32)}}


def test_roundtrip(tmp_path):
    t = make_tree()
    ckpt.save(tmp_path, 3, t)
    assert ckpt.latest_step(tmp_path) == 3
    r = ckpt.restore(tmp_path, 3, jax.tree.map(jnp.zeros_like, t))
    for x, y in zip(jax.tree.leaves(t), jax.tree.leaves(r)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_uncommitted_ignored(tmp_path):
    t = make_tree()
    ckpt.save(tmp_path, 1, t)
    ckpt.save(tmp_path, 2, t)
    (tmp_path / "step_00000002" / "_COMMITTED").unlink()
    assert ckpt.latest_step(tmp_path) == 1


def test_retention(tmp_path):
    t = make_tree()
    for s in (1, 2, 3, 4, 5):
        ckpt.save(tmp_path, s, t)
    ckpt.retain(tmp_path, keep=2)
    assert ckpt.latest_step(tmp_path) == 5
    steps = sorted(p.name for p in tmp_path.iterdir())
    assert steps == ["step_00000004", "step_00000005"]


def test_in_flight_save_is_not_a_checkpoint(tmp_path):
    """An async save holds its ``_COMMITTED`` marker in ``step_N.tmp``
    just before the rename; listing and retention must skip it."""
    t = make_tree()
    for s in (1, 2, 3):
        ckpt.save(tmp_path, s, t)
    tmp = tmp_path / "step_00000004.tmp"
    tmp.mkdir()
    (tmp / "_COMMITTED").touch()
    assert ckpt.latest_step(tmp_path) == 3
    ckpt.retain(tmp_path, keep=1)
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        "step_00000003", "step_00000004.tmp"]


def test_async_saver(tmp_path):
    t = make_tree()
    s = ckpt.AsyncSaver()
    s.save(tmp_path, 7, t)
    s.wait()
    assert ckpt.latest_step(tmp_path) == 7
    r = ckpt.restore(tmp_path, 7, jax.tree.map(jnp.zeros_like, t))
    np.testing.assert_array_equal(np.asarray(r["a"]), np.asarray(t["a"]))


def test_leaf_count_mismatch_raises(tmp_path):
    t = make_tree()
    ckpt.save(tmp_path, 1, t)
    with pytest.raises(AssertionError):
        ckpt.restore(tmp_path, 1, {"only": jnp.zeros((2,))})


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={nd}"
import sys
sys.path.insert(0, "{src}")
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import io as ckpt

mesh = jax.make_mesh(({nd},), ("data",))
t = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
if "{mode}" == "save":
    sh = NamedSharding(mesh, P("data", None))
    t = jax.tree.map(lambda x: jax.device_put(x, sh), t)
    ckpt.save("{dir}", 1, t)
else:
    sh = {{"w": NamedSharding(mesh, P(None, "data"))}}
    r = ckpt.restore("{dir}", 1, jax.eval_shape(lambda: t), shardings=sh)
    assert r["w"].sharding.spec == P(None, "data"), r["w"].sharding
    np.testing.assert_array_equal(np.asarray(r["w"]),
                                  np.arange(64).reshape(8, 8))
print("OK-{mode}")
"""


@pytest.mark.parametrize("nd_save,nd_load", [(8, 4), (4, 8)])
def test_elastic_restore_across_device_counts(tmp_path, nd_save, nd_load):
    src = str(Path(__file__).resolve().parent.parent / "src")
    for mode, nd in (("save", nd_save), ("load", nd_load)):
        script = ELASTIC_SCRIPT.format(nd=nd, src=src, dir=tmp_path,
                                       mode=mode)
        out = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True, timeout=120)
        assert f"OK-{mode}" in out.stdout, out.stderr[-2000:]
