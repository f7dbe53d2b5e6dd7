"""chip_smoke.py: the last-line contract, refusal without a TPU, and a CPU
rehearsal of every phase's control flow at a toy size (the real run is on
the chip; nothing here is a device result)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from fixtures import REPO, cpu_env, write_tiny_model, write_tiny_tokenizer

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

from dllama_tpu import quants  # noqa: E402


def test_last_line_is_exactly_the_contract():
    line = chip_smoke.last_line({"platform": "tpu", "kind": "TPU v5 lite",
                                 "count": 1, "compile_cache": "/x",
                                 "what": "device"})
    assert "\n" not in line
    obj = json.loads(line)
    assert set(obj) == {"ok", "device"} and obj["ok"] is True
    assert set(obj["device"]) == {"platform", "kind", "count"}
    assert obj["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1}
    assert json.dumps(obj) == line  # round-trips byte for byte


def test_refuses_without_a_tpu():
    """On the CPU the script exits non-zero and never prints ok:true (the
    first child's platform check fails before any model is synthesized)."""
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=cpu_env(1), capture_output=True, text=True,
                       timeout=300, cwd=REPO)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


def test_parent_side_never_imports_jax(tmp_path):
    """What the parent runs itself (cache directory, model synthesis) stays
    JAX-free: a parent that has touched JAX can take the chip from the
    children.  ``main`` holds itself to the same after its synth phase."""
    code = (
        "import sys; sys.path.insert(0, sys.argv[1]); import chip_smoke\n"
        "from dllama_tpu.hostenv import compile_cache_dir\n"
        "from dllama_tpu.synth import synth_model_files\n"
        "compile_cache_dir(); synth_model_files('cpu-tiny', sys.argv[2])\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n")
    r = subprocess.run([sys.executable, "-c", code, REPO, str(tmp_path)],
                       env=cpu_env(1), capture_output=True, text=True,
                       timeout=300, cwd=REPO)
    assert r.returncode == 0, r.stderr[-600:]


@pytest.fixture
def rehearsal_env(monkeypatch, tmp_path):
    for k, v in cpu_env(4).items():
        monkeypatch.setenv(k, v)
    m, t = str(tmp_path / "toy.m"), str(tmp_path / "toy.t")
    # shapes that allow tp=4 (n_kv_heads=4) with whole tiles per shard
    write_tiny_model(m, ftype=quants.Q40, dim=256, hidden_dim=512,
                     n_kv_heads=4, seq_len=256)
    write_tiny_tokenizer(t)
    return m, t, str(tmp_path)


def test_rehearse_kernels_phase(rehearsal_env, capfd):
    dev = chip_smoke.phase_kernels(600, rehearse=True)
    assert dev["platform"] == "cpu"
    rows = [json.loads(ln) for ln in capfd.readouterr().out.splitlines()]
    errs = [r for r in rows if "rel_err" in r]
    # six shapes × rows {1, 8, 16, 256}, and at one row (the grouped body, PR 50)
    # and at 8 and 16 (the sliced body, PR 62; the toy's ``moe_w2`` and ``wcls``
    # tiles of 1408 and 128 rows too) also against the float32 dequantization,
    # where the tile is whole vregs + a row's chosen experts in one launch,
    # against XLA and against the float32 dequantization (PR 58) +
    # dense/int8 fused attention + the fused walk at a chunk's 16 tokens a
    # slot, three head geometries, and at one token over the pool that holds
    # two heads of 64 a row + the live walk at prefill rows, dense/int8 × two
    # positions
    # + each of the walk's four (geometry, t) against its slots read one a
    # call, at tolerance 0 (PR 64: what crosses the grid's steps)
    alone = [r for r in errs if r["kernel"].endswith(".slot-alone")]
    assert [(r["geometry"]["heads"], r["t"], r["rel_err"], r["tol"])
            for r in alone] == [("mistral-7b", 16, 0.0, 0.0),
                                ("olmoe-1b-7b", 16, 0.0, 0.0),
                                ("lfm2-24b-a2b", 1, 0.0, 0.0),
                                ("lfm2-24b-a2b", 16, 0.0, 0.0)]
    errs = [r for r in errs if r not in alone]
    # + a pure-decode step's ring writes, the rule's form against the windows,
    # the four planes of Falcon-H1's mixer and of Granite's, bit for bit (PR 66)
    rings = [r for r in errs if r["kernel"] == "ring_put"]
    assert [(r["plane"], r["rel_err"], r["tol"]) for r in rings] == [
        (f"{m}.{k}", 0.0, 0.0) for m in ("falcon-h1-34b", "granite-4.0-h-small")
        for k in ("rk", "rv", "rg", "cz")]
    errs = [r for r in errs if r not in rings]
    # + a decoded token's recent rows, the launch over the live positions against
    # the XLA form over the ring: ``y``, ``gq`` and the whole read, both mixers (PR 67)
    walks = [r for r in errs if r["kernel"] == "ssm_recent_walk"]
    assert [(r["mixer"], r["what"], r["tol"]) for r in walks] == [
        (m, w, 2e-6) for m in ("falcon-h1-34b", "granite-4.0-h-small")
        for w in ("y", "gq", "read")]
    assert all(r["geometry"]["live"] == [1, 33, 64, 65, 96] for r in walks)
    errs = [r for r in errs if r not in walks]
    assert len(errs) == 54
    f32 = [r for r in errs if r["kernel"].endswith(".f32")]
    assert "q40.chosen_experts.f32" in [r["kernel"] for r in f32]
    assert len(f32) == 19 and all(r["tol"] == chip_smoke.Q40_F32_TOL for r in f32)
    assert sorted({r["rows"] for r in f32}) == [1, 8, 16]
    assert [r["chosen"] for r in errs if r["kernel"] == "q40.chosen_experts"] == [6]
    assert [r["geometry"]["heads"] for r in errs if r.get("t") == 16] == \
        ["mistral-7b", "olmoe-1b-7b", "lfm2-24b-a2b"]
    assert [(r["t"], r["geometry"]["dh"], r["geometry"]["pool_row"])
            for r in errs if r.get("geometry", {}).get("heads") == "lfm2-24b-a2b"] \
        == [(1, 64, [1, 128]), (16, 64, [1, 128])]
    assert all(r["rel_err"] <= r["tol"] for r in errs)


def test_rehearse_cli_and_server_phases(rehearsal_env, capfd):
    m, t, tmp = rehearsal_env
    res = chip_smoke.phase_cli(m, t, 600, steps=16, rehearse=True)
    assert res["generated_tokens"] == 16
    res = chip_smoke.phase_server(m, t, 600, tmp, slots=2, ctx=64, page=4,
                                  max_tokens=8, rehearse=True)
    assert res["drain_rc"] == 0 and res["greedy_replay_identical"]
    assert res["dispatch"].get("sample/sample-dev", 0) > 0
    assert '"ok": true' not in capfd.readouterr().out


def test_rehearse_packed_phase(rehearsal_env, capfd):
    m, _, _ = rehearsal_env
    chip_smoke.phase_packed(m, 600, rehearse=True)
    rows = [json.loads(ln) for ln in capfd.readouterr().out.splitlines()]
    cmp_ = next(r for r in rows if r.get("what") == "packed_step")
    assert (cmp_["valid_rows"], cmp_["run_rows"]) == (46, 64)
    assert cmp_["rel_err"] <= 1e-5 and cmp_["greedy_equal"]


def test_rehearse_tp_phase_on_virtual_devices(rehearsal_env, capfd):
    m, t, _ = rehearsal_env
    dev = chip_smoke.phase_tp(m, t, 600, tp=4, rehearse=True)
    assert dev["count"] >= 4
    rows = [json.loads(ln) for ln in capfd.readouterr().out.splitlines()]
    cmp_ = next(r for r in rows if r.get("what") == "compare")
    assert cmp_["weight_devices"] == 4 and cmp_["cache_devices"] == 4
    assert cmp_["tokens_tp"] == cmp_["tokens_tp1"]


def test_rehearse_moe_phases(rehearsal_env, capfd):
    """The mixture-of-experts pass: a seeded OLMoE-shaped file (64 experts, 8
    a token, toy widths) through the loader, ``moe_ffn``'s select-chosen,
    all-experts and grouped strategies against the XLA path (whose many-row
    form is the scan), then the paged server on that file (on the CPU: the XLA path)."""
    from dllama_tpu.io import mfile
    from dllama_tpu.synth import synth_model_files

    _, _, tmp = rehearsal_env
    m, t = synth_model_files("cpu-tiny-olmoe", tmp)
    spec = mfile.MFile(m).spec
    assert (spec.arch, spec.n_experts, spec.n_active_experts) == \
        (mfile.ARCH_OLMOE, 64, 8)
    chip_smoke.phase_moe(m, 600, rehearse=True)
    rows = [json.loads(ln) for ln in capfd.readouterr().out.splitlines()]
    errs = {(r["strategy"], r["rows"]): r for r in rows if "rel_err" in r}
    assert set(errs) == {("select-chosen", 1), ("all-experts", 16),
                         ("grouped", 256)}
    assert all(r["rel_err"] <= r["tol"] for r in errs.values())
    res = chip_smoke.phase_server(m, t, 600, tmp, slots=2, ctx=64, page=4,
                                  max_tokens=8, rehearse=True)
    assert res["drain_rc"] == 0 and res["greedy_replay_identical"]
    assert res["dispatch"].get("moe/scan", 0) > 0
