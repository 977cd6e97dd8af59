"""The architecture seam: everything that depends on a model's equations is
found by the name a configuration file gives (``"architecture": "<name>"``),
as a per-layer metric's reader is found by the metric's name.

``benchmark/architectures/<name>/`` holds four files, and the harness reaches
a model's weights, reference, counts and program mapping through them alone:

- ``program.py``: ``program_config(conf, **extra)``, the program's config
  object for this file, held against the file by ITS OWN table of keys
  (``agree``), and ``param_shardings(cfg, mesh, shapes)`` for a cell
  that trains. The only one of the four that imports ``kubeflow_tpu``.
- ``weights.py``: ``param_tree(conf, key, dtype)``, the tree the program's
  model expects, from the seed (``benchmark/weights.py`` jits it once).
- ``reference.py``: ``logits(params, tokens, conf, quant, last)`` and
  ``sequence_nll(params, tokens, conf, quant, remat)`` in plain
  ``jax.numpy`` and float32; it imports nothing of ``kubeflow_tpu``.
- ``counts.py``: ``params_total(conf)``, ``prefill_flops(conf, n)``,
  ``train_flops_per_token(conf, seq)``, ``decode_weight_bytes(conf,
  bytes_per_param)``, ``kv_bytes_per_token(conf, bytes_per_value)``.

The seam assumes nothing of the tree's shape (layers need not be alike, nor
one stack), of the cache (bytes a token come from ``counts``), of what the
file's counts mean beside the published ones (experts held, a sliced
vocabulary: the file states both and ``program.py`` checks its own), or of
which parameters the served path reads. Of a model's shape the harness itself
reads ``vocab_size``, what traffic draws its ids from.
"""

from __future__ import annotations

import functools
import os
import re
import types

from benchmark.manifest import HERE, ManifestError, load_module_file

ARCHITECTURES_DIR = os.path.join(HERE, "architectures")
PARTS = ("program", "weights", "reference", "counts")
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def name_of(conf: dict) -> str:
    name = conf.get("architecture")
    if not isinstance(name, str) or not _NAME.match(name):
        raise ManifestError(
            f"configuration {conf.get('name')!r} names no architecture "
            f"(\"architecture\": \"<name>\"); {ARCHITECTURES_DIR} has "
            f"{sorted(os.listdir(ARCHITECTURES_DIR))}")
    return name


@functools.lru_cache(maxsize=None)
def _load(name: str, part: str) -> types.ModuleType:
    path = os.path.join(ARCHITECTURES_DIR, name, part + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"architecture {name!r} has no {path}")
    return load_module_file("benchmark.architectures", name + "." + part,
                            path)


def part(conf: dict, which: str) -> types.ModuleType:
    """One of the four modules of the architecture ``conf`` names."""
    if which not in PARTS:
        raise ManifestError(f"an architecture has {PARTS}, not {which!r}")
    return _load(name_of(conf), which)


def agree(conf: dict, same: dict, absent: dict | None = None) -> None:
    """Hold the program's config against the configuration file: ``same``
    maps a key of the file to the value the program's config built from it
    has; ``absent`` gives what a key the file leaves out stands for. The
    table is the architecture's own (its ``program.py`` builds it)."""
    absent = absent or {}
    for key, value in same.items():
        if conf.get(key, absent.get(key)) != value:
            raise ManifestError(
                f"{key}: the configuration file says {conf.get(key)!r}, the "
                f"program's config built from it has {value!r}")
