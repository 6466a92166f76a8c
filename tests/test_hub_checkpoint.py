"""Model-hub checkpoint loading (ISSUE 12 acceptance).

safetensors I/O round-trips (cross-checked against the installed
reference implementation when present), the gpt2 name mapping is exact
(fused-qkv split, Conv1D/Linear layout detection, tied embeddings,
loud drops), sharded load places leaves by the existing partition
rules, and — the acceptance gate — the fixture checkpoint loaded
through the hub produces token-for-token identical greedy output to an
independent dense reference forward, for fp and int8-KV engines, gather
and fused attention. Everything offline against tests/fixtures."""

import dataclasses
import json
import os

import numpy as np
import pytest

from ray_tpu.models import make_forward
from ray_tpu.models.hub import (
    ByteBPETokenizer,
    SafetensorsFile,
    config_from_json,
    load_file,
    load_gpt2_params,
    load_model,
    save_file,
)
from ray_tpu.models.kv_paging import PagedDecodeEngine

FIXTURE = os.path.join(
    os.path.dirname(__file__), "fixtures", "hub_gpt2_tiny"
)


# ------------------------------------------------------------ safetensors


def test_safetensors_roundtrip(tmp_path):
    t = {
        "a": np.arange(24, dtype=np.float32).reshape(2, 3, 4),
        "b": np.ones((5,), np.int8),
        "c": np.zeros((2, 2), np.float16),
    }
    p = str(tmp_path / "t.safetensors")
    save_file(t, p, metadata={"k": "v"})
    with SafetensorsFile(p) as f:
        assert sorted(f.keys()) == ["a", "b", "c"]
        assert f.metadata == {"k": "v"}
        assert f.shape("a") == (2, 3, 4) and f.dtype("b") == np.int8
        for k in t:
            assert (f.tensor(k) == t[k]).all(), k
        # tensors are read-only mmap views
        with pytest.raises(ValueError):
            f.tensor("a")[0, 0, 0] = 1.0


def test_safetensors_cross_implementation(tmp_path):
    """Our writer reads with the reference lib and vice versa — the
    on-disk layout is the real safetensors format, not a lookalike."""
    stn = pytest.importorskip("safetensors.numpy")
    t = {"x": np.arange(6, dtype=np.float32).reshape(2, 3)}
    ours = str(tmp_path / "ours.safetensors")
    theirs = str(tmp_path / "theirs.safetensors")
    save_file(t, ours)
    assert (stn.load_file(ours)["x"] == t["x"]).all()
    stn.save_file(t, theirs)
    assert (load_file(theirs)["x"] == t["x"]).all()


def test_safetensors_rejects_corruption(tmp_path):
    p = str(tmp_path / "bad.safetensors")
    with open(p, "wb") as f:
        f.write(b"\xff" * 4)  # truncated header length
    with pytest.raises(ValueError):
        SafetensorsFile(p)
    import struct

    with open(p, "wb") as f:  # implausible header length
        f.write(struct.pack("<Q", 1 << 40))
    with pytest.raises(ValueError):
        SafetensorsFile(p)


def test_safetensors_reads_are_lazy(tmp_path):
    """tensor() materializes one tensor; nothing reads the whole file.
    (Proxy check: a file with one CORRUPT entry still serves the intact
    ones — eager full-file validation would fail them all.)"""
    p = str(tmp_path / "t.safetensors")
    save_file({"good": np.ones(4, np.float32),
               "big": np.zeros((1 << 16,), np.float32)}, p)
    with SafetensorsFile(p) as f:
        # truncate the declared shape mismatch case artificially via a
        # direct entry edit: 'big' claims more bytes than its span
        f._entries["big"]["shape"] = [1 << 20]
        assert (f.tensor("good") == 1).all()
        with pytest.raises(ValueError):
            f.tensor("big")
        # offsets escaping the data section (negative / past-the-end)
        # must never reinterpret header bytes as weights
        f._entries["good"]["data_offsets"] = [-16, 0]
        with pytest.raises(ValueError, match="data section"):
            f.tensor("good")
        f._entries["good"]["data_offsets"] = [1 << 30, (1 << 30) + 16]
        with pytest.raises(ValueError, match="data section"):
            f.tensor("good")


# ---------------------------------------------------------- name mapping


def test_config_from_json(tmp_path):
    cfg = config_from_json(os.path.join(FIXTURE, "config.json"))
    assert cfg.mlp_variant == "gelu" and cfg.tie_embeddings
    assert cfg.n_kv_heads == cfg.n_heads
    assert cfg.d_head * cfg.n_heads == cfg.d_model
    # a checkpoint trained with a different activation must refuse, not
    # serve silently wrong logits (the MLP is tanh-gelu only)
    cj = json.load(open(os.path.join(FIXTURE, "config.json")))
    cj["activation_function"] = "relu"
    bad = tmp_path / "config.json"
    bad.write_text(json.dumps(cj))
    with pytest.raises(ValueError, match="activation_function"):
        config_from_json(str(bad))


def test_qkv_split_and_layout(tmp_path):
    """Build a checkpoint from KNOWN q/k/v blocks and verify the loader
    splits the fused c_attn into exactly those — in Conv1D layout and,
    transposed, in Linear layout."""
    cfg = config_from_json(os.path.join(FIXTURE, "config.json"))
    E, H, D, L, F, V = (cfg.d_model, cfg.n_heads, cfg.d_head,
                        cfg.n_layers, cfg.d_ff, cfg.vocab_size)
    rng = np.random.default_rng(7)
    q = rng.standard_normal((E, E)).astype(np.float32)
    k = rng.standard_normal((E, E)).astype(np.float32)
    v = rng.standard_normal((E, E)).astype(np.float32)
    fused = np.concatenate([q, k, v], axis=1)  # [E, 3E] Conv1D
    # NON-symmetric square c_proj: the crux of layout detection — a
    # square matrix carries no orientation signal, so the loader must
    # use the file-global verdict probed on the non-square c_attn
    proj = rng.standard_normal((E, E)).astype(np.float32)
    fc = rng.standard_normal((E, F)).astype(np.float32)
    down = rng.standard_normal((F, E)).astype(np.float32)

    def write(dirname, transpose):
        d = tmp_path / dirname
        d.mkdir()
        tensors = {"wte.weight": rng.standard_normal((V, E)).astype(np.float32),
                   "ln_f.weight": np.ones(E, np.float32)}

        def lay(w):  # Conv1D stores [in, out]; Linear stores [out, in]
            return w.T.copy() if transpose else w

        for i in range(L):
            p = f"h.{i}."
            tensors[p + "attn.c_attn.weight"] = lay(fused)
            tensors[p + "attn.c_proj.weight"] = lay(proj)
            tensors[p + "ln_1.weight"] = np.ones(E, np.float32)
            tensors[p + "ln_2.weight"] = np.ones(E, np.float32)
            tensors[p + "mlp.c_fc.weight"] = lay(fc)
            tensors[p + "mlp.c_proj.weight"] = lay(down)
        save_file(tensors, str(d / "model.safetensors"))
        return str(d)

    loaded = []
    for transpose in (False, True):
        path = write(f"t{int(transpose)}", transpose)
        params, out_cfg, report = load_gpt2_params(path, cfg=cfg)
        assert (params["layers"]["wq"][0].reshape(E, E) == q).all(), transpose
        assert (params["layers"]["wk"][0].reshape(E, E) == k).all()
        assert (params["layers"]["wv"][0].reshape(E, E) == v).all()
        # wo reshapes [E, E] -> [H, D, E] head-major; the SQUARE c_proj
        # must orient by the global layout, not a per-tensor guess
        assert params["layers"]["wo"].shape == (L, H, D, E)
        assert (params["layers"]["wo"][0].reshape(E, E) == proj).all(), (
            "square attn.c_proj mis-oriented under "
            + ("Linear" if transpose else "Conv1D") + " layout"
        )
        assert (params["layers"]["w_up"][0] == fc).all()
        assert (params["layers"]["w_down"][0] == down).all()
        assert out_cfg.tie_embeddings  # no lm_head in this checkpoint
        loaded.append(params)
    # the two layouts load to the SAME param tree
    for key in loaded[0]["layers"]:
        assert (loaded[0]["layers"][key] == loaded[1]["layers"][key]).all(), key


def test_fixture_loads_and_reports(tmp_path):
    params, cfg, report = load_gpt2_params(FIXTURE)
    # every weight matrix mapped; positions + every bias dropped LOUDLY
    assert "wpe.weight" in report["dropped"]
    assert all(n.endswith(".bias") or n == "wpe.weight"
               for n in report["dropped"]), report["dropped"]
    assert report["tied_embeddings"] and "unembed" not in params
    L, E = cfg.n_layers, cfg.d_model
    assert params["embed"].shape == (cfg.vocab_size, E)
    assert params["layers"]["wq"].shape == (L, E, cfg.n_heads, cfg.d_head)
    assert params["layers"]["w_up"].shape == (L, E, cfg.d_ff)
    assert "w_gate" not in params["layers"]  # gelu variant: no gate

    # unknown tensors fail loudly under strict (the default)
    import shutil

    broken = tmp_path / "broken"
    shutil.copytree(FIXTURE, broken)
    extra = load_file(str(broken / "model.safetensors"))
    extra["mystery.weight"] = np.zeros(3, np.float32)
    save_file(extra, str(broken / "model.safetensors"))
    with pytest.raises(ValueError, match="mystery"):
        load_gpt2_params(str(broken))
    _, _, rep = load_gpt2_params(str(broken), strict=False)
    assert "mystery.weight" in rep["dropped"]


def test_untied_checkpoint_gets_unembed(tmp_path):
    import shutil

    d = tmp_path / "untied"
    shutil.copytree(FIXTURE, d)
    t = load_file(str(d / "model.safetensors"))
    rng = np.random.default_rng(3)
    cfg0 = config_from_json(os.path.join(FIXTURE, "config.json"))
    lm = rng.standard_normal(
        (cfg0.vocab_size, cfg0.d_model)).astype(np.float32)
    t["lm_head.weight"] = lm
    save_file(t, str(d / "model.safetensors"))
    params, cfg, report = load_gpt2_params(str(d))
    assert not cfg.tie_embeddings and not report["tied_embeddings"]
    assert (params["unembed"] == lm.T).all()


def test_load_model_bundle():
    b = load_model(FIXTURE)
    assert b.model_id == "hub_gpt2_tiny"
    assert isinstance(b.tokenizer, ByteBPETokenizer)
    assert b.eos_id == b.tokenizer.eos_id is not None
    assert b.cfg.vocab_size >= len(b.tokenizer)
    assert b.params_source.endswith("model.safetensors")


def test_sharded_load_places_leaves_by_partition_rules():
    """mesh+rules load device_puts each leaf with the SAME logical
    sharding the rule table gives params everywhere else — and the
    sharded params decode identically to the host-loaded ones."""
    from ray_tpu.parallel import MeshSpec, PRESET_RULES, build_mesh

    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    rules = PRESET_RULES["fsdp_tp"]
    b_host = load_model(FIXTURE)
    b_shard = load_model(FIXTURE, mesh=mesh, rules=rules)
    wq = b_shard.params["layers"]["wq"]
    # fsdp_tp: embed dim shards on fsdp, heads on tp
    spec = wq.sharding.spec
    assert "tp" in str(spec), spec
    # the fixture's 321-token vocab does not divide the tp axis: the
    # loader zero-pads it to the next multiple and records the pad so
    # the samplers mask those ids (greedy equality below proves it)
    assert b_shard.cfg.vocab_pad > 0
    assert b_shard.cfg.vocab_size % 2 == 0
    assert b_shard.params["embed"].shape[0] == b_shard.cfg.vocab_size
    prompt = b_host.tokenizer.encode("The quick brown fox")

    def greedy(bundle, mesh=None, rules=None):
        eng = PagedDecodeEngine(
            bundle.cfg, bundle.params, max_batch_size=2, block_tokens=8,
            eos_id=bundle.eos_id, mesh=mesh, rules=rules,
        )
        tok, done = eng.admit(0, {"tokens": prompt, "max_new_tokens": 8})
        out = [tok]
        while not done:
            tok, done = eng.step([0])[0]
            out.append(tok)
        return out

    assert greedy(b_host) == greedy(b_shard, mesh=mesh, rules=rules)


# ------------------------------------------------------ greedy parity gate


def _dense_reference(bundle, prompt, n):
    """INDEPENDENT reference: the full (non-cached, non-paged) forward
    re-run over the growing sequence, argmax at the last position —
    shares no decode/cache/paging machinery with the engines under test."""
    fwd = make_forward(bundle.cfg)
    ids = list(prompt)
    out = []
    for _ in range(n):
        logits = fwd(bundle.params, np.asarray(ids, np.int32)[None])
        t = int(np.argmax(np.asarray(logits)[0, -1]))
        out.append(t)
        if bundle.eos_id is not None and t == bundle.eos_id:
            break
        ids.append(t)
    return out


def _engine_greedy(bundle, prompt, n, **engine_kwargs):
    eng = PagedDecodeEngine(
        bundle.cfg, bundle.params, max_batch_size=2, block_tokens=8,
        eos_id=bundle.eos_id, **engine_kwargs,
    )
    tok, done = eng.admit(0, {"tokens": prompt, "max_new_tokens": n})
    out = [tok]
    while not done:
        toks, done = eng.step([0])[0]
        out.extend(toks if isinstance(toks, (list, tuple)) else [toks])
    eng.release(0)
    return out


@pytest.fixture(scope="module")
def bundle():
    return load_model(FIXTURE)


@pytest.fixture(scope="module")
def fixture_prompts(bundle):
    with open(os.path.join(FIXTURE, "reference.json"), encoding="utf-8") as f:
        ref = json.load(f)
    return [bundle.tokenizer.encode(p) for p in ref["prompts"]]


@pytest.mark.parametrize("kv_dtype,attn", [
    ("fp", "gather"),
    ("fp", "fused"),
    ("int8", "gather"),
    ("int8", "fused"),
])
def test_greedy_parity_vs_dense_reference(bundle, fixture_prompts,
                                          kv_dtype, attn):
    """THE acceptance gate: hub-loaded weights through every engine
    variant produce token-for-token the independent dense reference's
    greedy output on the fixture prompt set."""
    n = 10
    for prompt in fixture_prompts[:3]:
        ref = _dense_reference(bundle, prompt, n)
        got = _engine_greedy(
            bundle, prompt, n,
            kv_cache_dtype=kv_dtype, attention_impl=attn,
        )
        assert got == ref, (kv_dtype, attn, prompt[:6])


def test_greedy_parity_with_speculation(bundle, fixture_prompts):
    """The n-gram drafter over REAL token ids must not change greedy
    output (acceptance compares against the model's own argmax)."""
    prompt = fixture_prompts[0]
    ref = _dense_reference(bundle, prompt, 16)
    got = _engine_greedy(bundle, prompt, 16, speculative_k=4,
                         drafter="ngram")
    assert got == ref


def test_hub_decode_decodes_to_text(bundle, fixture_prompts):
    """End-of-pipeline sanity: engine tokens detokenize to text (the
    serving path's contract) and the eos id never leaks as text."""
    out = _engine_greedy(bundle, fixture_prompts[0], 8)
    text = bundle.tokenizer.decode(
        [t for t in out if t != bundle.eos_id]
    )
    assert isinstance(text, str)
