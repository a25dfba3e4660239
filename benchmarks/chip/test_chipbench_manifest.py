"""BENCHMARK.json and the files the benchmark finds by name.

Every cell's configuration, traffic and limits, and every metric's
reader, are found by the names BENCHMARK.json gives, so a cell, a
configuration or a metric is added by files and entries alone.  Each
configuration keeps its model's published widths.
"""
import dataclasses
import json
import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import bench  # noqa: E402
import fleet  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _configs(manifest):
    return {c["name"]: bench.load_json(ROOT, c["file"])
            for c in manifest["configs"]}


def test_every_cell_finds_its_files(manifest):
    for wl in manifest["workloads"]:
        cfg = bench.load_json(HERE, "configs", wl["config"] + ".json")
        traffic = bench.load_json(HERE, "workloads", wl["traffic"] + ".json")
        limits = bench.load_json(HERE, "limits", wl["name"] + ".json")
        assert cfg["name"] == wl["config"]
        assert traffic["experts"] >= 1 and traffic["batch"]
        assert set(limits) == {"mismatch_share", "outside_tol"}
        assert wl["chips"] in (1, 4)
        assert bench.find_cell(manifest, wl["name"]) is wl
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_metric_has_a_reader(manifest):
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert callable(bench.reader(m["name"])), m["name"]


def test_reader_finds_nothing_to_read_without_a_trace():
    run = {"setup_s": 1.0, "trace": None, "traced_merge_bytes": None,
           "peak": None, "batches": [{"wall_s": 2.0, "io_bytes": 10,
                                      "expert_bytes": 4,
                                      "jobs": [{"seconds": 1.5}]}]}
    assert bench.reader("kernel_roofline")(run) is None
    assert bench.reader("device_idle_share")(run) is None
    assert bench.reader("merge_s")(run) == 2.0
    assert bench.reader("service_s")(run) == 0.5


def test_per_layer_metrics_move_what_their_cells_report(manifest):
    reported = {x["name"] for x in bench.cell_metrics(manifest, False)}
    traced = {x["name"] for x in bench.cell_metrics(manifest, True)}
    for m in manifest["per_layer"]:
        assert "workloads" not in m  # every cell reports every metric
        assert m["moves"] in reported
        assert m["name"] in traced


def test_every_cell_reports_enough(manifest):
    e2e = {m["name"] for m in bench.cell_metrics(manifest, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert all("workloads" not in m for m in manifest["end_to_end"])
    assert bench.cell_metrics(manifest, True)


def test_names_and_units(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append(entry["name"])
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    for wl in manifest["workloads"]:
        assert NAME.match(wl["config"]) and NAME.match(wl["traffic"])
        assert len(wl["why"]) <= 200
    for c in manifest["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith(manifest["paths"][0] + "/")
    for m in manifest["per_layer"]:
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    assert len(names) == len(set(names))


def _params_of_model_tree(arch: str, n_layers: int) -> int:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import build_model

    cfg = dataclasses.replace(get_config(arch), n_layers=n_layers)
    tree = jax.eval_shape(build_model(cfg).init,
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if "lm_head" in name or "unembed" in name:
            continue  # the published configs tie the head to the embedding
        total += int(np.prod(leaf.shape))
    return total


@pytest.mark.parametrize("name", ["qwen2-1.5b", "granite-3-8b"])
def test_inventory_keeps_published_widths(manifest, name):
    cfg = _configs(manifest)[name]
    inv = dict(fleet.inventory(cfg))
    d, dff = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    assert cfg["tie_word_embeddings"] and "lm_head.weight" not in inv
    assert inv["model.embed_tokens.weight"] == (cfg["vocab_size"], d)
    assert inv["model.norm.weight"] == (d,)
    for i in range(cfg["num_hidden_layers"]):
        p = "model.layers.%d." % i
        assert inv[p + "self_attn.q_proj.weight"] == (q, d)
        assert inv[p + "self_attn.k_proj.weight"] == (kv, d)
        assert inv[p + "self_attn.v_proj.weight"] == (kv, d)
        assert inv[p + "self_attn.o_proj.weight"] == (d, q)
        assert inv[p + "mlp.gate_proj.weight"] == (dff, d)
        assert inv[p + "mlp.up_proj.weight"] == (dff, d)
        assert inv[p + "mlp.down_proj.weight"] == (d, dff)
        assert inv[p + "input_layernorm.weight"] == (d,)
        assert inv[p + "post_attention_layernorm.weight"] == (d,)
        if cfg["attention_bias"]:
            assert inv[p + "self_attn.q_proj.bias"] == (q,)
            assert inv[p + "self_attn.k_proj.bias"] == (kv,)
            assert inv[p + "self_attn.v_proj.bias"] == (kv,)
    per_layer = 12 if cfg["attention_bias"] else 9
    assert len(inv) == 2 + per_layer * cfg["num_hidden_layers"]
    entry = [c for c in manifest["configs"] if c["name"] == name][0]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert cfg["reduced"]["num_hidden_layers"]["kept"] == cfg[
        "num_hidden_layers"]
    n = sum(int(np.prod(s)) for s in inv.values())
    assert n == _params_of_model_tree(cfg["repro_config"],
                                      cfg["num_hidden_layers"])
