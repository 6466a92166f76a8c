"""A block is a file: `common.load_block` finds a configuration's mapping,
FLOPs count and float32 reference in benchmark/blocks/<name>.py by the
file's name, so a new architecture comes as new files and no edit. The
proof is made on a copy of benchmark/ that gets three files and loses or
changes none.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402

# the llama block with the output head tied to the embedding: one key the
# llama block does not know, mapped onto a TransformerConfig field it never
# sets, and a reference the llama one cannot give (the tree has no `unembed`)
TOY_BLOCK = '''
"""Toy block: llama with a tied head (`tied_head` in the file)."""
from benchmark import common

LLAMA = common.load_block({"block": "llama"})
required_train_flops_per_token = LLAMA.required_train_flops_per_token


def transformer_kwargs(conf):
    rest = {k: v for k, v in conf.items() if k != "tied_head"}
    return dict(LLAMA.transformer_kwargs(rest),
                tie_embeddings=bool(conf["tied_head"]))


def _untied(params):
    return dict(params, unembed=params["embed"].T)


def ref_logits(params, tokens, conf, positions=None):
    return LLAMA.ref_logits(_untied(params), tokens, conf, positions)


def ref_loss(params, tokens, conf):
    return LLAMA.ref_loss(_untied(params), tokens, conf)
'''
TOY_CONF = {
    "name": "toy", "source": "none: a test", "block": "toy", "tied_head": True,
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 160,
    "vocab_size": 257, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
    "hidden_act": "silu", "bias": False, "tie_word_embeddings": False,
    "run": {"max_seq_len": 64}, "reduced": [],
}
TOY_CELL = {"kind": "train", "config": "toy", "chips": 1, "seq_len": 48}


def _files(root) -> dict:
    out = {}
    for d, dirs, names in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for n in names:
            path = os.path.join(d, n)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = f.read()
    return out


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """benchmark/ copied to a directory of the test's own, and the harness
    pointed at it."""
    root = str(tmp_path / "benchmark")
    shutil.copytree(common.BENCH_DIR, root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    monkeypatch.setattr(common, "BENCH_DIR", root)
    return root


def test_a_new_block_is_three_files_and_no_edit(bench_copy):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.transformer import (
        TransformerConfig, init_params, make_loss_fn,
    )

    before = _files(bench_copy)
    added = {os.path.join("blocks", "toy.py"): TOY_BLOCK,
             os.path.join("configs", "toy.json"): json.dumps(TOY_CONF),
             os.path.join("workloads", "toy.pretrain.json"): json.dumps(TOY_CELL)}
    for rel, text in added.items():
        with open(os.path.join(bench_copy, rel), "w") as f:
            f.write(text)

    cell = common.load_workload("toy.pretrain")
    conf = common.load_config(cell["config"])
    block = common.load_block(conf)
    assert block.__file__ == os.path.join(bench_copy, "blocks", "toy.py")
    # the llama block, handed this file, refuses it by the key it lacks
    with pytest.raises(ValueError, match="tied_head"):
        block.LLAMA.transformer_kwargs(conf)

    cfg = TransformerConfig(**block.transformer_kwargs(conf),
                            dtype=jnp.float32, attention="dense", remat=False)
    assert cfg.tie_embeddings and cfg.d_model == 64 and cfg.max_seq_len == 64
    assert block.required_train_flops_per_token(conf, cell["seq_len"]) > 0
    params = init_params(jax.random.PRNGKey(7), cfg)
    assert "unembed" not in params
    tokens = np.random.default_rng(7).integers(
        0, 257, size=(2, cell["seq_len"] + 1))
    batch = {"tokens": jnp.asarray(tokens), "mask": jnp.ones_like(tokens)}
    with jax.default_matmul_precision("highest"):
        want = float(make_loss_fn(cfg)(params, batch))
    got = block.ref_loss(params, tokens, conf)
    assert abs(got - want) <= 1e-5 * want   # float32 both sides
    logits = np.asarray(block.ref_logits(params, tokens[0, :-1], conf, [0, 47]))
    assert logits.shape == (2, 257)

    after = _files(bench_copy)
    assert sorted(set(after) - set(before)) == sorted(added)
    assert all(after[rel] == data for rel, data in before.items())


# what common.transformer_kwargs gave for the three files before the mapping
# moved into blocks/llama.py, and the GFLOP a token at the cells' 4096
# (test_yardstick.py works the same counts out by hand)
WIDTHS = {
    "internlm2": dict(vocab_size=92544, d_model=2048, n_heads=16, n_kv_heads=8,
                      d_head=128, d_ff=8192),
    "mistral": dict(vocab_size=32768, d_model=4096, n_heads=32, n_kv_heads=8,
                    d_head=128, d_ff=14336),
}


@pytest.mark.parametrize("name,widths,layers,gflop", [
    ("internlm2-1.8b", "internlm2", 24, 11.4051),
    ("internlm2-1.8b-l12", "internlm2", 12, 6.2712),
    ("mistral-7b-v0.3-l6", "mistral", 6, 9.2612),
])
def test_the_real_files_resolve_to_llama(name, widths, layers, gflop):
    conf = common.load_config(name)
    assert "block" not in conf
    block = common.load_block(conf)
    assert block.__file__ == os.path.join(common.BENCH_DIR, "blocks", "llama.py")
    assert block.transformer_kwargs(conf) == dict(
        WIDTHS[widths], n_layers=layers, rope_theta=1e6, max_seq_len=4096,
        tie_embeddings=False)
    assert block.required_train_flops_per_token(conf, 4096) / 1e9 == \
        pytest.approx(gflop, abs=1e-4)


def test_a_key_the_block_does_not_know_is_refused_by_name():
    conf = common.load_config("internlm2-1.8b")
    for key, value in (("num_experts", 64), ("norm_topk_prob", False)):
        with pytest.raises(ValueError, match=key):
            common.load_block(conf).transformer_kwargs({**conf, key: value})


@pytest.mark.parametrize("missing", common.BLOCK_NAMES)
def test_a_block_without_one_of_the_four_names_fails_by_it(
        missing, tmp_path, monkeypatch):
    os.makedirs(tmp_path / "blocks")
    with open(tmp_path / "blocks" / "partial.py", "w") as f:
        f.write("".join(f"def {n}(*a, **kw): pass\n"
                        for n in common.BLOCK_NAMES if n != missing))
    monkeypatch.setattr(common, "BENCH_DIR", str(tmp_path))
    with pytest.raises(AttributeError, match=f"partial.py lacks {missing}$"):
        common.load_block({"block": "partial"})
    with pytest.raises(FileNotFoundError, match="nowhere.py"):
        common.load_block({"block": "nowhere"})


def test_the_driver_loads_a_block_without_jax():
    """run.py's process loads the block for its mapping (serving) and its
    FLOPs count (training) and "never opens a JAX backend": the reference's
    imports wait until a reference function is called."""
    code = (
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "from benchmark import common\n"
        "conf = common.load_config('internlm2-1.8b-l12')\n"
        "block = common.load_block(conf)\n"
        "block.transformer_kwargs(conf)\n"
        "print(block.required_train_flops_per_token(conf, 4096))\n"
        "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n")
    out = subprocess.run([sys.executable, "-c", code, common.ROOT],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert float(out.stdout) / 1e9 == pytest.approx(6.2712, abs=1e-4)
