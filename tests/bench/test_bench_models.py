"""Model modules: the plain module pinned to the values the benchmark
gave before its GQA code moved into ``bench/models/plain.py``, every
module's interface, and a configuration whose model module exists only as
a new file, carried through a whole run by the harness."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench_tiny import DATA, ROOT
from bench import manifest, weights

PLAIN = manifest.model({})
TINY = manifest.load_json(DATA / "configs" / "tiny-nemo.json")
SEED = 2 ** 33 + 5            # wider than 32 bits, as a run's may be
# (sum, sum of magnitudes, element 7) of one draw of each leaf
DRAWS = {
    ("wq", 1): (0.6415727138519287, 69.15149807929993, -0.02392578125),
    ("attn_norm", 0): (64.01563322544098, 64.01563322544098,
                       0.9788966774940491),
    ("w_down", 1): (0.17533843964338303, 71.23066618293524,
                    -0.01031494140625),
    ("embed", 0): (-0.9571136385202408, 566.7938309460878,
                   -0.000568389892578125),
    ("unembed", 0): (5.075790187343955, 567.9451936390251,
                     0.01080322265625),
    ("final_norm", 0): (64.13388395309448, 64.13388395309448,
                        0.9265885949134827),
}
INTERFACE = ("model_shape", "LEAF_IDS", "program_config", "params_fn",
             "layer_weights", "layer", "final_norm", "model_flops",
             "attention_flops", "decode_attention_bytes", "ATTENTION_KERNEL")


def test_a_configuration_without_a_model_key_is_plain():
    assert manifest.model(TINY) is PLAIN
    assert manifest.model({"model": "plain"}) is PLAIN


def test_plain_counts_are_pinned():
    shape = PLAIN.model_shape(TINY)
    assert PLAIN.model_flops(shape, 1000, 123456, 77) == 215711744.0
    assert PLAIN.decode_attention_bytes(shape, 123456, 77) == 31644160.0
    assert PLAIN.attention_flops(shape, 123456) == 63209472.0


@pytest.mark.parametrize("leaf,index", list(DRAWS), ids=[
    f"{n}-{i}" for n, i in DRAWS])
def test_plain_draws_are_pinned(leaf, index):
    shape = PLAIN.model_shape(TINY)
    root = weights.root_key(SEED)
    if leaf in weights.LEAF_IDS:
        rows = 512 if leaf != "final_norm" else 0
        x = weights.shared_draw(root, shape, leaf, index, rows)
    else:
        x = PLAIN.block_draw(root, shape, leaf, index)
    x = np.asarray(x, np.float64)
    assert (float(x.sum()), float(np.abs(x).sum()),
            float(x.ravel()[7])) == DRAWS[leaf, index]


def test_plain_reference_gap_is_pinned():
    from bench.reference import logit_gaps
    rng = np.random.default_rng(0)
    samples = [(rng.integers(0, 512, 40).tolist(),
                rng.integers(0, 512, 9).tolist()),
               (rng.integers(0, 512, 90).tolist(),
                rng.integers(0, 512, 5).tolist())]
    g = logit_gaps(PLAIN, PLAIN.model_shape(TINY), 7, samples,
                   controls=("fp8",))
    assert g["positions"] == 14
    assert g["program"] == pytest.approx(0.8138906955718994, rel=1e-6)
    assert g["controls"]["fp8"] == pytest.approx(0.0, abs=1e-6)


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (ROOT / "bench" / "models").glob("*.py")
    if p.stem != "__init__"))
def test_every_model_module_has_the_interface(name):
    mod = manifest.model({"model": name})
    assert [k for k in INTERFACE if not hasattr(mod, k)] == []
    # its own draw ids, none of the shared leaves'
    assert not set(mod.LEAF_IDS) & set(weights.LEAF_IDS)
    assert not set(mod.LEAF_IDS.values()) & set(weights.LEAF_IDS.values())
    assert len(set(mod.LEAF_IDS.values())) == len(mod.LEAF_IDS)


_RUN = """
import sys
checkout = sys.argv[1]
sys.path[:0] = [checkout, checkout + "/tests/bench"]
import time
import jax
import repro.configs as registry
import bench
from bench.harness import run_cell
from bench_tiny import PEAKS, tiny_cell
assert bench.__file__.startswith(checkout), bench.__file__
real = registry.get_config
registry.get_config = lambda n: registry.smoke_config(real(n))
cell = tiny_cell("tiny-copy")
out = run_cell(cell, 13, 2.0, False, time.perf_counter(), PEAKS,
               jax.devices())
assert out["correct"] and out["failed"] == 0, out
assert "bench._loaded.model_plain_copy" in sys.modules
assert "bench._loaded.model_plain" not in sys.modules
print("NEW-MODEL-OK", out["checks"]["max_logit_gap"]["value"])
"""


def test_a_new_model_module_is_taken_as_a_new_file_alone(tmp_path):
    """A checkout whose benchmark files are the repository's, plus one new
    model module (a copy of ``plain`` under another name) and one new
    configuration naming it: the harness loads, builds, serves and checks
    it with no other file changed."""
    ignore = shutil.ignore_patterns("__pycache__")
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=ignore)
    shutil.copytree(ROOT / "tests" / "bench", tmp_path / "tests" / "bench",
                    ignore=ignore)
    shutil.copy(ROOT / "bench" / "models" / "plain.py",
                tmp_path / "bench" / "models" / "plain_copy.py")
    conf = dict(TINY, model="plain_copy")
    (tmp_path / "tests" / "bench" / "data" / "configs" /
     "tiny-copy.json").write_text(json.dumps(conf))
    r = subprocess.run([sys.executable, "-c", _RUN, str(tmp_path)],
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=str(ROOT / "src")),
                       capture_output=True, text=True, timeout=600,
                       cwd=tmp_path)
    assert "NEW-MODEL-OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
