"""The six readers of the program's memory account on made-up inputs: the
value, a gauge that reads 0 kept as 0.0, nothing at all from a program
without the gauge (the parent of the PR that added it), the fullest of four
devices; and the manifest's rules for their six entries."""

import importlib
import json
import os

import pytest
from conftest import BENCH, ROOT

READERS = ("hbm_found_gb", "hbm_params_gb", "hbm_programs_gb",
           "hbm_temp_peak_gb", "hbm_headroom_gb", "host_rss_peak_gb")
ACCOUNT = {"found": 1.5e9, "params": 12.0e9, "cache": 0.8e9,
           "resident_idle": 14.5e9, "programs": 0.2e9, "limit": 15.75e9}
AFTER = {"hbm_account_bytes": ACCOUNT, "hbm_bytes_peak": {"0": 15.0e9},
         "hbm_bytes_in_use": {"0": 14.5e9}, "host_rss_peak_bytes": 31.25e9}
# the parent's program: the two totals it always had, no account, no RSS
PARENT = {"hbm_bytes_peak": {"0": 13.5e9}, "hbm_bytes_in_use": {"0": 13.3e9},
          "param_bytes_resident": {"0": 12.0e9}}
WANT = {"hbm_found_gb": 1.5, "hbm_params_gb": 12.0, "hbm_programs_gb": 0.2,
        "hbm_temp_peak_gb": 0.5, "hbm_headroom_gb": 0.75,
        "host_rss_peak_gb": 31.25}


def _ctx(after: dict) -> dict:
    return {"after": after, "before": {}, "peaks": {"hbm_bytes": 16e9}}


def _read(name: str, after: dict):
    return importlib.import_module(name).read(_ctx(after))


@pytest.mark.parametrize("name", READERS)
def test_memory_reader_value(name):
    assert _read(name, AFTER) == pytest.approx(WANT[name], rel=1e-12)


@pytest.mark.parametrize("name", READERS)
def test_memory_reader_is_none_on_a_program_without_the_gauge(name):
    assert _read(name, PARENT) is None
    assert _read(name, {}) is None


@pytest.mark.parametrize("name,zeroed", [
    ("hbm_found_gb", {"found": 0.0}), ("hbm_params_gb", {"params": 0.0}),
    ("hbm_programs_gb", {"programs": 0.0}),
    ("hbm_temp_peak_gb", {"resident_idle": 15.0e9}),
    ("hbm_headroom_gb", {"limit": 15.0e9})])
def test_memory_reader_keeps_a_zero(name, zeroed):
    value = _read(name, dict(AFTER, hbm_account_bytes={**ACCOUNT, **zeroed}))
    assert value == 0.0 and value is not None


def test_host_rss_reader_keeps_a_zero_and_drops_a_null():
    assert _read("host_rss_peak_gb", dict(AFTER, host_rss_peak_bytes=0.0)) == 0.0
    assert _read("host_rss_peak_gb", dict(AFTER, host_rss_peak_bytes=None)) is None


def test_memory_readers_take_the_fullest_of_four_devices():
    four = dict(AFTER, hbm_bytes_peak={"0": 7.0e9, "1": 7.5e9, "2": 7.25e9,
                                       "3": 7.0e9},
                hbm_account_bytes=dict(ACCOUNT, resident_idle=7.0e9))
    assert _read("hbm_temp_peak_gb", four) == pytest.approx(0.5)
    assert _read("hbm_headroom_gb", four) == pytest.approx(15.75 - 7.5)


def test_headroom_falls_back_to_the_published_hbm():
    no_limit = {k: v for k, v in ACCOUNT.items() if k != "limit"}
    assert _read("hbm_headroom_gb", dict(AFTER, hbm_account_bytes=no_limit)) \
        == pytest.approx(1.0)


def test_an_account_not_yet_idle_reads_its_owners_alone():
    early = {k: ACCOUNT[k] for k in ("found", "params", "cache", "limit")}
    after = dict(AFTER, hbm_account_bytes=early)
    assert _read("hbm_found_gb", after) == 1.5
    assert _read("hbm_programs_gb", after) is None
    assert _read("hbm_temp_peak_gb", after) is None


def test_the_six_entries_follow_the_manifests_rules():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    last = manifest["per_layer"][-len(READERS):]
    assert [m["name"] for m in last] == list(READERS)   # at the end, in order
    layers = {m["layer"] for m in manifest["per_layer"][:-len(READERS)]}
    for m in last:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert (m["unit"], m["source"], m["moves"]) \
            == ("GB", "program_counter", "setup_s")
        assert m["workloads"] == cells          # every accepted cell, by name
        assert m["layer"] in layers             # a layer the manifest names
        assert m["better"] == ("higher" if m["name"] == "hbm_headroom_gb"
                               else "lower")
    assert [m["layer"] for m in last] == ["device"] * 5 + [
        "engine (runtime/engine.py, decode_loop.py)"]
