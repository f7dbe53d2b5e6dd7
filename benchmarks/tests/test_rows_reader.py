"""``serve_rows_fill_pct`` on made-up counters: valid over run across kinds,
the window's delta, and nothing from a program without the counter."""

import importlib

BEFORE = {"sched_step_rows": {"valid/decode": 160, "run/decode": 160,
                              "valid/mixed": 460, "run/mixed": 640}}
AFTER = {"sched_step_rows": {"valid/decode": 480, "run/decode": 480,
                             "valid/mixed": 1380, "run/mixed": 1920,
                             "valid/verify": 40, "run/verify": 64}}


def _read(ctx):
    return importlib.import_module("serve_rows_fill_pct").read(ctx)


def test_fill_is_valid_over_run_of_the_window():
    # decode 320 / 320, mixed 920 / 1280, verify 40 / 64
    assert _read({"before": BEFORE, "after": AFTER}) == 100.0 * 1280 / 1664
    assert _read({"before": {}, "after": AFTER}) == 100.0 * 1900 / 2464


def test_no_counter_or_no_step_reads_nothing():
    parent = {"sched_steps": {"decode": 180, "mixed": 40}}
    assert _read({"before": parent, "after": parent}) is None
    assert _read({"before": AFTER, "after": AFTER}) is None
