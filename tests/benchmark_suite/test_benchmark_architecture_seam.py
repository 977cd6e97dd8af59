"""The architecture seam (benchmark/architecture.py): a configuration names
its architecture, and weights, reference, counts and program mapping are
found by that name. Pins that today's three configurations were MOVED behind
it and not rewritten: the weights of a seed and the counts of a file are what
the parent's ``benchmark/weights.py`` and ``benchmark/flops.py`` gave
(recorded_parent_pins.json, written from commit 793570b before the move)."""

import ast
import hashlib
import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark import architecture, correctness
from benchmark import manifest as mf
from benchmark.weights import make_params

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "recorded_parent_pins.json")) as f:
    PINS = json.load(f)
ARCHITECTURES = sorted(os.listdir(architecture.ARCHITECTURES_DIR))
CONFIG_FILES = sorted(f for f in os.listdir(os.path.join(mf.HERE, "configs"))
                      if f.endswith(".json"))
SERVED_TYPE = {"rehearsal-tiny": "bfloat16", "rehearsal-tiny-moe": "bfloat16",
               "rehearsal-tiny-fsdp4": "float32"}


def tree_digest(tree) -> str:
    """sha256 over every leaf's path, type, shape and bytes, by path."""
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(np.ascontiguousarray(a).view(np.uint8).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("pin", sorted(PINS["weights"]))
def test_a_seed_gives_the_weights_it_gave_before_the_move(pin):
    config, seed = pin.split("/")
    conf = mf.load_json(f"benchmark/configs/{config}.json")
    got = tree_digest(make_params(conf, int(seed), SERVED_TYPE[config]))
    assert got == PINS["weights"][pin]


@pytest.mark.parametrize("config", sorted(PINS["counts"]))
def test_counts_of_the_real_files_are_the_parents(config):
    conf = mf.load_config(mf.load_manifest(), config)
    counts = architecture.part(conf, "counts")
    got = {"params_total": counts.params_total(conf),
           "prefill_flops_512": counts.prefill_flops(conf, 512),
           "prefill_flops_8192": counts.prefill_flops(conf, 8192),
           "train_flops_per_token_4096":
               counts.train_flops_per_token(conf, 4096),
           "decode_weight_bytes_2": counts.decode_weight_bytes(conf, 2),
           "kv_bytes_per_token_2": counts.kv_bytes_per_token(conf, 2)}
    assert got == PINS["counts"][config]       # the same integers, exactly


def _imports(path: str) -> set[str]:
    with open(path) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module or "")
    return names


@pytest.mark.parametrize("name", ARCHITECTURES)
def test_an_architecture_is_four_files_and_one_of_them_knows_the_program(
        name):
    folder = os.path.join(architecture.ARCHITECTURES_DIR, name)
    files = {f for f in os.listdir(folder) if f.endswith(".py")}
    assert files == {p + ".py" for p in architecture.PARTS}
    for part in ("reference", "weights", "counts"):
        bad = {m for m in _imports(os.path.join(folder, part + ".py"))
               if m.split(".")[0] == "kubeflow_tpu"}
        assert not bad, f"{name}/{part}.py imports {bad}"
    conf = {"architecture": name}
    ref, counts = (architecture.part(conf, p) for p in ("reference", "counts"))
    assert callable(ref.logits) and callable(ref.sequence_nll)
    assert callable(architecture.part(conf, "weights").param_tree)
    assert callable(architecture.part(conf, "program").program_config)
    for fn in ("params_total", "prefill_flops", "train_flops_per_token",
               "decode_weight_bytes", "kv_bytes_per_token"):
        assert callable(getattr(counts, fn)), f"{name}/counts.py: {fn}"


def test_loading_every_reference_loads_nothing_of_the_program():
    """Not only the files' own import lines: what they import in turn
    (benchmark/reference.py, benchmark/weights.py, the seam)."""
    code = (
        "import sys\n"
        "from benchmark import architecture\n"
        f"for name in {ARCHITECTURES!r}:\n"
        "    for part in ('reference', 'weights', 'counts'):\n"
        "        architecture.part({'architecture': name}, part)\n"
        "bad = sorted(m for m in sys.modules\n"
        "             if m.startswith('kubeflow_tpu'))\n"
        "assert not bad, bad\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=mf.ROOT,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


@pytest.mark.parametrize("file", CONFIG_FILES)
def test_every_configuration_names_an_architecture_that_exists(file):
    conf = mf.load_json(f"benchmark/configs/{file}")
    assert architecture.name_of(conf) in ARCHITECTURES


def test_a_file_without_an_architecture_or_with_an_unknown_one_is_refused():
    with pytest.raises(mf.ManifestError, match="names no architecture"):
        architecture.part({"name": "x"}, "counts")
    with pytest.raises(mf.ManifestError, match="has no"):
        architecture.part({"architecture": "never-written"}, "counts")
    with pytest.raises(mf.ManifestError, match="not 'kernels'"):
        architecture.part({"architecture": "mistral"}, "kernels")
    with pytest.raises(mf.ManifestError, match="vocab_size"):
        architecture.agree({"vocab_size": 256}, {"vocab_size": 512})
    architecture.agree({"vocab_size": 256}, {"vocab_size": 256, "experts": 0},
                       absent={"experts": 0})


MODEL_SHAPE_KEYS = re.compile(
    r"num_local_experts|num_key_value_heads|intermediate_size|"
    r"num_attention_heads|hidden_size|head_dim|num_experts_per_tok")


def test_the_harness_reads_a_models_shape_only_inside_architectures():
    """Outside ``benchmark/architectures/`` the harness's code names none of
    a model's shape keys (it reads ``vocab_size``, what traffic draws ids
    from, and one reader ``num_hidden_layers``)."""
    found = []
    folders = [mf.HERE, mf.LAYER_METRICS_DIR]
    for folder in folders:
        for name in sorted(os.listdir(folder)):
            if not name.endswith(".py"):
                continue
            with open(os.path.join(folder, name)) as f:
                for n, line in enumerate(f, 1):
                    if MODEL_SHAPE_KEYS.search(line):
                        found.append(f"{name}:{n}: {line.strip()}")
    assert not found, found


def test_the_comparison_tells_the_two_architectures_apart():
    """The rehearsal's Gemma-shaped tree through ``mistral``'s reference
    (plain norms, SiLU, no embedding scale, no soft-cap) is far over the
    limit its own reference is held to: the seam hands each configuration
    ITS equations."""
    conf = mf.load_json("benchmark/configs/rehearsal-tiny-gemma.json")
    params = make_params(conf, 5, "bfloat16")
    tokens = correctness.check_tokens(5, 0, 64, conf["vocab_size"])
    own = correctness.reference_logits(params, tokens, conf, last=64)
    other = correctness.reference_logits(
        params, tokens, {**conf, "architecture": "mistral"}, last=64)
    err = float(np.median(correctness.position_errors(other, own)))
    assert err > 10 * conf["correctness"]["limits"]["prefill_logit_err"]
