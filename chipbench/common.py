"""Small helpers shared by the harness, its loops and its readers.

Nothing here imports JAX or the program, so the readers and the tests of
the trace reduction stay cheap to import.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# names of the harness's own host spans, written into the profiler trace
# with jax.profiler.TraceAnnotation; trace_reduce attributes idle gaps to them
SPANS = ("client.generate", "client.submit", "engine.flush", "job.logdet")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """Import ``chipbench/<kind>/<name>.py``: builders, loops and metric
    readers are found by name, never by an import list that a later PR
    would have to edit. A name with dots (a metric's) is loaded by path."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{path} does not exist")
    if name.isidentifier():
        return importlib.import_module(f"chipbench.{kind}.{name}")
    mod_name = f"chipbench.{kind}.{name.replace('.', '__')}"
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[mod_name]


def seed32(seed: int, stream: int = 0) -> int:
    """A 31-bit integer derived from any whole-number seed and a stream
    tag, for PRNG keys that want a small integer."""
    import numpy as np

    ss = np.random.SeedSequence([int(seed) & (2 ** 64 - 1), int(stream)])
    return int(ss.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)


def rng(seed: int, stream: int = 0):
    import numpy as np

    return np.random.default_rng([int(seed) & (2 ** 64 - 1), int(stream)])


def nearest_rank(values, q: float) -> float:
    """The q-th quantile (0 < q <= 1) by nearest rank: an actual sample,
    so a tail is the tail of the requests and not an interpolation."""
    xs = sorted(values)
    if not xs:
        return math.nan
    k = max(1, math.ceil(q * len(xs)))
    return float(xs[k - 1])
