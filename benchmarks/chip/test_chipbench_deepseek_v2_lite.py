"""The DeepSeek-V2-Lite configuration: its tensors at published widths, and
whole runs of the harness over a DeepSeek-shaped fleet on the CPU.

Every tensor's shape follows from the keys of the published config.json
that the configuration file copies; the MoE layer's attention, router,
shared experts and routed experts are tied to the repository's
``deepseek-v2-lite-16b`` model tree, which has no dense layer, so layer 0
is checked against config.json alone.
"""
import dataclasses
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import bench  # noqa: E402
import control  # noqa: E402
import fleet  # noqa: E402

NAME = "deepseek-v2-lite"
SEED = 2 ** 35 + 17


def _config():
    return bench.load_json(HERE, "configs", NAME + ".json")


def published_inventory(c):
    """[(tensor, shape)] of a DeepseekV2ForCausalLM checkpoint with the
    keys of ``c`` (config.json's), routed experts 0 .. n_routed_experts-1
    held in each MoE layer, in the configuration's order."""
    d, heads = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    kv, moe = c["kv_lora_rank"], c["moe_intermediate_size"]
    shared = c["n_shared_experts"] * moe
    out = [("model.embed_tokens.weight", (c["vocab_size"], d))]
    for i in range(c["num_hidden_layers"]):
        p = "model.layers.%d." % i
        out += [(p + "self_attn.q_proj.weight", (heads * (nope + rope), d)),
                (p + "self_attn.kv_a_proj_with_mqa.weight", (kv + rope, d)),
                (p + "self_attn.kv_a_layernorm.weight", (kv,)),
                (p + "self_attn.kv_b_proj.weight", (heads * (nope + v), kv)),
                (p + "self_attn.o_proj.weight", (d, heads * v))]
        if i < c["first_k_dense_replace"]:
            dff = c["intermediate_size"]
            out += [(p + "mlp.gate_proj.weight", (dff, d)),
                    (p + "mlp.up_proj.weight", (dff, d)),
                    (p + "mlp.down_proj.weight", (d, dff))]
        else:
            for j in range(c["n_routed_experts"]):
                q = p + "mlp.experts.%d." % j
                out += [(q + "gate_proj.weight", (moe, d)),
                        (q + "up_proj.weight", (moe, d)),
                        (q + "down_proj.weight", (d, moe))]
            out += [(p + "mlp.gate.weight",
                     (c["reduced"]["n_routed_experts"]["published"], d)),
                    (p + "mlp.shared_experts.gate_proj.weight", (shared, d)),
                    (p + "mlp.shared_experts.up_proj.weight", (shared, d)),
                    (p + "mlp.shared_experts.down_proj.weight", (d, shared))]
        out += [(p + "input_layernorm.weight", (d,)),
                (p + "post_attention_layernorm.weight", (d,))]
    out += [("model.norm.weight", (d,)),
            ("lm_head.weight", (c["vocab_size"], d))]
    return out


def test_every_tensor_has_its_published_shape():
    cfg = _config()
    assert fleet.inventory(cfg) == published_inventory(cfg)
    inv = dict(fleet.inventory(cfg))
    # spot checks against the published model.safetensors
    assert inv["model.layers.1.mlp.experts.15.gate_proj.weight"] == (1408, 2048)
    assert inv["model.layers.4.mlp.shared_experts.up_proj.weight"] == (
        2816, 2048)
    assert inv["model.layers.2.mlp.gate.weight"] == (64, 2048)
    assert inv["model.layers.0.self_attn.kv_a_proj_with_mqa.weight"] == (
        576, 2048)
    assert inv["model.layers.3.self_attn.kv_b_proj.weight"] == (4096, 512)
    assert inv["model.layers.0.mlp.down_proj.weight"] == (2048, 10944)
    assert inv["lm_head.weight"] == (102400, 2048)
    assert "model.layers.1.mlp.experts.16.gate_proj.weight" not in inv
    assert not cfg["tie_word_embeddings"] and cfg["q_lora_rank"] is None


def test_tensor_count_and_cut():
    cfg = _config()
    inv = fleet.inventory(cfg)
    # embedding, final norm, head; the dense layer; four MoE layers
    assert len(inv) == 3 + 10 + 4 * 59 == 249
    assert cfg["num_hidden_layers"] == 5 and cfg["n_routed_experts"] == 16
    red = cfg["reduced"]
    assert red["num_hidden_layers"]["published"] == 27
    assert red["n_routed_experts"]["published"] == 64
    assert {k: v["kept"] for k, v in red.items()} == {
        "num_hidden_layers": 5, "n_routed_experts": 16}
    assert "EP-4" in cfg["deployment"]
    assert sum(int(np.prod(s)) for _, s in inv) == 1_178_886_656
    assert cfg["first_k_dense_replace"] == 1


def _model_tree(n_layers):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import build_model

    arch = dataclasses.replace(get_config(_config()["repro_config"]),
                               n_layers=n_layers)
    tree = jax.eval_shape(build_model(arch).init,
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_moe_layer_matches_the_model_tree():
    cfg = _config()
    inv = dict(fleet.inventory(cfg))
    tree = _model_tree(1)

    def tree_params(prefix):
        return sum(int(np.prod(s[1:])) for n, s in tree.items()
                   if n.startswith(prefix))

    def layer_params(layer, parts):
        p = "model.layers.%d." % layer
        return sum(int(np.prod(s)) for n, s in inv.items()
                   if n.startswith(p) and any(x in n for x in parts))

    for layer in range(1, cfg["num_hidden_layers"]):
        assert layer_params(layer, ["self_attn."]) == tree_params("attn/")
        assert layer_params(layer, ["shared_experts."]) == tree_params(
            "ffn/ws_")
        assert layer_params(layer, ["mlp.gate.weight"]) == tree_params(
            "ffn/router")
        # the tree holds all 64 experts; this shard holds 16 of them
        per_expert = tree_params("ffn/w_") // tree["ffn/w_gate"][1]
        assert layer_params(layer, ["mlp.experts."]) == (
            cfg["n_routed_experts"] * per_expert)
        assert layer_params(layer, ["layernorm.weight"]) - (
            cfg["kv_lora_rank"]) == tree_params("ln1") + tree_params("ln2")
    # layer 0 is dense (first_k_dense_replace 1): config.json alone
    dff, d = cfg["intermediate_size"], cfg["hidden_size"]
    assert layer_params(0, ["mlp."]) == 3 * dff * d == 67_239_936
    assert layer_params(0, ["self_attn."]) == tree_params("attn/")


# ------------------------------------------------ whole runs, tiny size
def tiny_config():
    """A DeepSeek-shaped configuration at a tiny size: the same tensor
    kinds and names, a dense layer and 2 MoE layers of 8 experts."""
    c = {k: v for k, v in _config().items() if k != "tensors"}
    c.update({"name": "tiny-deepseek", "block_size": 4096,
              "hidden_size": 64, "intermediate_size": 96,
              "num_attention_heads": 4, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
              "moe_intermediate_size": 24, "n_routed_experts": 8,
              "num_hidden_layers": 3, "vocab_size": 300})
    c["reduced"] = dict(c["reduced"], n_routed_experts={"published": 8})
    c["tensors"] = [[n, list(s)] for n, s in published_inventory(c)]
    return c


TRAFFIC = {"ties-k4-b50": NAME + ".ties-k4-b50",
           "ta-k8": "qwen2-1.5b.ta-k8"}


@pytest.mark.parametrize("traffic_name", sorted(TRAFFIC))
def test_a_deepseek_shaped_run_is_correct(tmp_path, traffic_name):
    cfg = tiny_config()
    assert len(cfg["tensors"]) == 3 + 10 + 2 * (11 + 3 * 8)
    traffic = bench.load_json(HERE, "workloads", traffic_name + ".json")
    limits = bench.load_json(HERE, "limits", TRAFFIC[traffic_name] + ".json")
    t_start = time.time()
    workdir = str(tmp_path / "ws")
    bench.make_fleet(cfg, traffic, SEED, workdir)
    result = bench.run_cell({"name": cfg["name"], "chips": 1}, cfg, traffic,
                            limits, [], SEED, 0.05, False, platform="cpu",
                            expect_backend="jnp-cpu", workdir=workdir,
                            t_start=t_start)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    (control_numbers,) = control.control_numbers(cfg, traffic, SEED)
    assert any(control_numbers[k] > limits[k]["limit"] for k in limits), (
        control_numbers)


# ------------------------------------------------------ the new readers
def _run(jobs):
    return {"setup_s": 1.0, "trace": None, "traced_merge_bytes": None,
            "peak": None, "batches": [{"wall_s": 4.0, "io_bytes": 10,
                                       "expert_bytes": 4, "jobs": jobs}]}


def _job(counters, spans):
    return {"seconds": 1.0, "trace": {
        "job": "j", "counters": counters,
        "spans": {n: {"count": 2, "seconds": s, "self_seconds": s}
                  for n, s in spans.items()}}}


def test_fd_opens_and_tensor_sync_readers():
    fd_opens, sync = bench.reader("fd_opens"), bench.reader("tensor_sync_s")
    span = "mergepipe.bg.tensor_sync"
    run = _run([_job({"fd_opens": 1245}, {span: 0.5}),
                _job({"fd_opens": 1243}, {span: 0.7})])
    assert fd_opens(run) == 1244
    assert sync(run) == pytest.approx(0.6)
    # a program without the counter and the span (the parent of this
    # change) reads nothing; so does a run without job traces
    older = _run([_job({"h2d_bytes": 5}, {"mergepipe.commit": 1.0})])
    assert fd_opens(older) is None and sync(older) is None
    bare = _run([{"seconds": 1.0}])
    assert fd_opens(bare) is None and sync(bare) is None
