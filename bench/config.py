"""What a configuration file names for the benchmark's own use.

* ``reference``: the path of the plain reference module, such as
  ``bench/reference.py``, imported as the module of that path.
* ``stack`` (optional, outside ``model``, which goes to the program
  unchanged): ``lead``, the kind of the model's ``first_k_dense`` leading
  layers, and ``pattern``, the kinds repeated over the rest (the model's
  ``block_pattern`` by default).
* A block kind ``k`` is the file ``bench/blocks/<k>.py``, found by name as
  the metric readers are.  It holds ``leaves(m)`` and ``forward(p, x, m,
  ar)`` for the reference, and for the arithmetic the weight parameters a
  layer holds (``held_params``) and those each token multiplies
  (``active_params``), its norm and bias parameters (``norm_params``) and
  its forward sequence-mixing operations per token at ``keys`` positions
  (``mix_flops``).

One layer list serves the reference and the arithmetic alike; it is built
from the configuration's keys and imports nothing of the program.
"""
from __future__ import annotations

import importlib
import importlib.util
from functools import lru_cache
from pathlib import Path
from typing import Dict, List, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def model(config: Dict) -> Dict:
    """The model dict the reference and the arithmetic read: the file's
    ``model``, with its ``stack`` when it states one."""
    m = config["model"]
    return dict(m, stack=config["stack"]) if "stack" in config else m


def reference(config: Dict):
    """The reference module the configuration names."""
    rel = config["reference"]
    if not rel.endswith(".py") or not (ROOT / rel).is_file():
        raise FileNotFoundError(f"the configuration names the reference "
                                f"{rel!r}, which is no file under {ROOT}")
    return importlib.import_module(rel[:-3].replace("/", "."))


def plan(m: Dict) -> List[Tuple[Tuple[str, ...], int]]:
    """[(kinds, repeats)]: ``first_k_dense`` leading layers of the kind
    ``stack.lead``, then the pattern repeated over the rest of the depth,
    with a partial last period unrolled."""
    st = m.get("stack", {})
    pat = tuple(st.get("pattern", m["block_pattern"]))
    n, lead = m["num_layers"], m.get("first_k_dense", 0)
    out = []
    if lead:
        if "lead" not in st:
            raise ValueError(f"first_k_dense is {lead}, but the "
                             f"configuration's stack names no lead kind")
        out.append(((st["lead"],) * lead, 1))
        n -= lead
    reps, rem = divmod(n, len(pat))
    if reps:
        out.append((pat, reps))
    if rem:
        out.append((pat[:rem], 1))
    return out


def kinds(m: Dict) -> List[str]:
    """The kind of every layer, in order."""
    return [k for pat, reps in plan(m) for k in pat * reps]


@lru_cache(maxsize=None)
def block(kind: str):
    """The module ``bench/blocks/<kind>.py``."""
    path = BENCH / "blocks" / f"{kind}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no block file {path} for kind {kind!r}")
    spec = importlib.util.spec_from_file_location(
        "bench_block_" + kind.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
