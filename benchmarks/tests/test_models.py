"""The architecture modules (``benchmarks/models/``) and the one function that
finds them.  The golden values were taken on the parent of the PR that moved
the dense code there (PR 30), from ``mformat.header``/``plan``/``_tensor_bytes``,
``cost.py`` and ``run.py model_shape`` as they then were: a model file this
benchmark makes, and every cost a reader divides by, is what it was.  JAX-free.
"""

import hashlib
import json
import os

import pytest

from conftest import BENCH, ROOT
from harness import cost, mformat, models


def sha(data) -> str:
    return hashlib.sha256(data).hexdigest()


def config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# (header sha256, sha256 of repr(plan), tensors, file bytes,
#  {chips: (weight_bytes, kv_bytes_per_token, the same at 1 byte an element,
#           step_bytes at 3000.5 live tokens, step_flops at 16 rows)})
GOLDEN = {
    "mistral-7b": (
        "2ce0002ba8adb81c89abb6bcc752dadd26f3c269e3a93aafd3c0f2cc561787f3",
        "8d3a016be197579c18b321038d17dee68799fcc37e82d784e082fda659e3267e",
        291, 4539302008,
        {1: (4001366016.0, 131072.0, 65536.0, 4394647552.0, 229206392832.0),
         4: (1000341504.0, 32768.0, 16384.0, 1098661888.0, 57301598208.0)}),
    "yi-34b": (
        "85b3d514698acd3fbace1edd4266042828e57cc859d737aa642bf621c7738e73",
        "0a57097603576cc306006e031b5c91fe2ccda1e81b9f23dbde14126fbca43d7c",
        543, 20923707512,
        {1: (19085230080.0, 245760.0, 122880.0, 19822632960.0, 1090899353600.0),
         4: (4771307520.0, 61440.0, 30720.0, 4955658240.0, 272724838400.0)}),
}
# tensors made from (seed 23, index) alone: (index, shape, ftype, dead rows)
TENSOR_BYTES = {
    "q40": ((5, (64, 96), mformat.Q40, 0),
            "c153120a78d4db77a76ac4ad5a7d45f289a7afc83dddabfdcff9915d675fa9fe"),
    "f32 vector": ((8, (64,), mformat.F32, 0),
                   "12049f5615fa223e58153d7a39423c1e313a64b98157fd44992f2d0cec377139"),
    "f32 matrix": ((0, (40, 64), mformat.F32, 0),
                   "2ac49d99dfdc0708b3fd6e56c57f6d8cfd1e3d1f59b651c561263668705e2474"),
    "wcls, 3 dead rows": ((20, (40, 64), mformat.Q40, 3),
                          "be1292b8241dabc33bc93202a87c3e098fd79b596f29144711777e0062612f56"),
}
MIXTRAL = dict(model="moe", hidden_size=4096, intermediate_size=14336,
               num_hidden_layers=32, num_attention_heads=32,
               num_key_value_heads=8, num_local_experts=8, num_experts_per_tok=2,
               vocab_size=32000, max_position_embeddings=32768, rope_theta=1e6)


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_dense_layout_is_the_parents(name):
    head, plan, n_tensors, n_bytes, _ = GOLDEN[name]
    cfg = config(name)
    model = models.for_config(cfg)
    shape = model.shape(cfg)
    assert sha(model.header(shape)) == head
    tensors = model.plan(shape)
    assert sha(repr(tensors).encode()) == plan
    assert len(tensors) == n_tensors
    assert tensors[-1][3] + tensors[-1][4] == n_bytes
    toy = dict(shape, **model.REHEARSE)
    assert (toy["dim"], toy["hidden_dim"], toy["n_layers"], toy["n_heads"],
            toy["n_kv_heads"], toy["vocab_size"]) == (256, 512, 2, 8, 4, 2048)
    assert (toy["seq_len"], toy["rope_theta"]) == (shape["seq_len"], shape["rope_theta"])


@pytest.mark.parametrize("what", sorted(TENSOR_BYTES))
def test_tensor_bytes_are_the_parents(what):
    (index, shp, ftype, dead), want = TENSOR_BYTES[what]
    assert sha(mformat._tensor_bytes(23, index, shp, ftype, dead).tobytes()) == want


@pytest.mark.parametrize("chips", (1, 4))
@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_dense_cost_is_the_parents(name, chips):
    cfg = config(name)
    weights, kv, kv1, step, flops = GOLDEN[name][4][chips]
    for rows in (1, 16, 13.7):  # a dense step streams every weight whatever the rows
        assert cost.weight_bytes(cfg, chips, rows) == weights
        assert cost.step_bytes(cfg, 3000.5, chips, rows) == step
    assert cost.weight_bytes(cfg, chips) == weights
    assert cost.kv_bytes_per_token(cfg, chips) == kv
    assert cost.kv_bytes_per_token(cfg, chips, 1) == kv1
    assert cost.step_bytes(cfg, 3000.5, chips) == step
    assert cost.step_flops(cfg, 16, 3000.5, chips) == flops


def test_dense_shape_checks_head_dim():
    with pytest.raises(SystemExit, match="head_dim"):
        models.load("dense").shape(dict(config("mistral-7b"), head_dim=64))


def test_every_configuration_resolves_to_a_whole_module():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for entry in manifest["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        model = models.for_config(cfg)
        assert model is models.load(cfg.get("model", models.DEFAULT))
        for name in models.EXPORTS:
            assert hasattr(model, name), (entry["name"], name)
        assert model.shape(cfg)["dim"] == cfg["hidden_size"]
    for name in sorted(os.listdir(models.MODELS)):  # those no configuration names yet
        if name.endswith(".py"):
            models.load(name[:-3])


def test_a_missing_module_fails_with_its_path():
    with pytest.raises(FileNotFoundError) as err:
        models.for_config({"model": "no-such-architecture"})
    assert os.path.join(models.MODELS, "no-such-architecture.py") in str(err.value)


def test_moe_cost_follows_the_routing(tmp_path):
    """Mixtral-8x7B's published sizes: a one-row step reads 2 of 8 experts a
    layer, many rows nearly all 8, and a row always multiplies through 2."""
    moe = models.for_config(MIXTRAL)
    att = 2 * 4096 * 4096 + 2 * 4096 * 1024
    router, one, head = 8 * 4096, 3 * 4096 * 14336, 32000 * 4096
    q40 = 18 / 32
    assert cost.weight_bytes(MIXTRAL, 1, 1) == pytest.approx(
        (32 * (att + router + 2 * one) + head) * q40, rel=1e-12)
    read4 = 8 * (1 - (1 - 2 / 8) ** 4)  # 5.47 distinct experts at 4 rows
    assert cost.weight_bytes(MIXTRAL, 1, 4) == pytest.approx(
        (32 * (att + router + read4 * one) + head) * q40, rel=1e-12)
    assert cost.weight_bytes(MIXTRAL, 1, 1) < cost.weight_bytes(MIXTRAL, 1, 4) \
        < cost.weight_bytes(MIXTRAL, 1, 256) <= (32 * (att + router + 8 * one) + head) * q40
    assert cost.weight_bytes(MIXTRAL, 4, 16) == cost.weight_bytes(MIXTRAL, 1, 16) / 4
    assert cost.kv_bytes_per_token(MIXTRAL) == 2 * 32 * 8 * 128 * 2
    assert cost.step_bytes(MIXTRAL, 1000, 1, 4) == (
        cost.weight_bytes(MIXTRAL, 1, 4) + cost.kv_bytes_per_token(MIXTRAL) * 1000)
    assert cost.step_flops(MIXTRAL, 4, 1000) == pytest.approx(
        2.0 * ((32 * (att + router + 2 * one) + head) * 4 + 2 * 32 * 4096 * 1000), rel=1e-12)
    shape = moe.shape(MIXTRAL)
    assert (shape["n_experts"], shape["n_active_experts"]) == (8, 2)
    (tmp_path / "header.m").write_bytes(moe.header(shape))
    assert mformat.read_header(str(tmp_path / "header.m")) == dict(
        shape, version=1, arch=0xABCD02, hidden_act=1, weights_ftype=mformat.Q40,
        rope_theta=int(shape["rope_theta"]))
    assert len(moe.plan(shape)) == 1 + 32 * (4 + 1 + 3 * 8 + 2) + 2
    with pytest.raises(SystemExit, match="num_experts_per_tok"):
        moe.shape(dict(MIXTRAL, num_experts_per_tok=9))

