"""Model families, found by name: one directory of files for each.

A configuration file names its family (``"family": "dense_gqa"``), and the
family's files are ``bench/families/<family>/``:

* ``reference.py``, the plain reference of the family; it imports nothing
  of the program under test:

  - ``dims(cfg)``: the model's sizes from its configuration file, a dict
    of hashable values that holds ``"family"`` and ``"vocab"``;
  - ``draw(m, key)``: every weight, float32, none of them zero; the head
    is ``lm_head`` (d_model, vocab) or, tied, the transpose of ``embed``;
  - ``hidden(m, w, tokens, lowp)``: the final normed hidden states of one
    sequence (``lowp``: the control's float8 arithmetic).

* ``program.py``, the family as the program takes it; it imports the
  program only inside its functions:

  - ``model_config(cfg, m)``: the program's ``ModelConfig``;
  - ``program_params(m, w)``: ``draw``'s weights in the program's layout.

* ``costs.py``, the work of one token or one plan:

  - ``matmul_flops_per_token(m)``: weight matmul operations of one token
    through every layer;
  - ``head_flops(m)``: operations of the head on one sampled token;
  - ``KERNELS``: device-op family name (as ``bench/trace.py op_family``
    prints it) -> ``(m, items) -> (flops, bytes)``, the work that kernel
    has to do for one plan.  Only work that ``matmul_flops_per_token``
    leaves out (attention over the cache) belongs here: ``costs.step_flops``
    adds these operations to the matmuls'.

The shared parts -- norms, rotary embeddings, attention in query blocks,
the head, the lowp arithmetic -- live in ``bench/reference.py`` and
``bench/costs.py``.  A family joins by its directory alone: nothing outside
it names one.
"""
from __future__ import annotations

import functools
import importlib.util
import os

FAMILIES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "families")
PARTS = ("reference", "program", "costs")


def check(cfg: dict) -> str:
    """The family a configuration file names; an error where it names none,
    or one whose files are missing."""
    name = cfg.get("family")
    if not name:
        raise ValueError(f"configuration {cfg.get('name')!r} names no family")
    missing = [p for p in PARTS if not os.path.exists(_path(name, p))]
    if missing:
        raise ValueError(f"configuration {cfg.get('name')!r}: family {name!r} "
                         f"has no {', '.join(p + '.py' for p in missing)} "
                         f"in {os.path.join(FAMILIES, name)}")
    return name


def module(name: str, part: str):
    """``bench/families/<name>/<part>.py``, loaded once."""
    return _load(_path(name, part))


def _path(name: str, part: str) -> str:
    return os.path.join(FAMILIES, name, f"{part}.py")


@functools.lru_cache(maxsize=None)
def _load(path: str):
    name, part = path.split(os.sep)[-2:]
    spec = importlib.util.spec_from_file_location(
        f"bench_family_{name}_{part[:-3]}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
