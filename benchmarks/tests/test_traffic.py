"""The generator: the same seed gives the same jobs, strata balance every
block, the tokenizer model is exact, and the end-to-end arithmetic."""

import json
import os

from conftest import BENCH
from harness import e2e, tokens, traffic


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_same_seed_same_jobs_other_seed_other_jobs():
    mix = _mix("serve-closed")
    a = [traffic.job(mix, 7, j, 32768, 1) for j in range(40)]
    b = [traffic.job(mix, 7, j, 32768, 1) for j in range(40)]
    c = [traffic.job(mix, 8, j, 32768, 1) for j in range(40)]
    assert a == b and a != c
    assert all(3 <= t < 32768 for job in a for t in job[0]["ids"])


def test_every_block_of_jobs_carries_the_same_lengths():
    mix = _mix("serve-closed")
    blocks = []
    for seed in (1, 2):
        for blk in (0, 3):
            jobs = [traffic.job(mix, seed, blk * traffic.STRATA + k, 32768, 1)[0]
                    for k in range(traffic.STRATA)]
            blocks.append((sorted(len(j["ids"]) for j in jobs),
                           sorted(j["max_tokens"] for j in jobs)))
    assert all(b == blocks[0] for b in blocks)
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= n + 1 <= hi for n in blocks[0][0])


def test_shared_prefix_and_sessions():
    mix = dict(_mix("serve-closed"), sharing={"prefix_tokens": 20, "groups": 2},
               session={"turns": 3, "turn_tokens": {"dist": "fixed", "value": 5}})
    j0, j1, j2 = (traffic.job(mix, 1, j, 32768, 1) for j in range(3))
    assert j0[0]["ids"][:20] == j2[0]["ids"][:20] != j1[0]["ids"][:20]
    assert len(j0) == 3 and j0[1]["ids"][:len(j0[0]["ids"])] == j0[0]["ids"]
    assert len(j0[2]["ids"]) == len(j0[0]["ids"]) + 10


def test_open_loop_arrivals_follow_the_rate():
    mix = {"arrivals": {"kind": "poisson", "rate_per_s": 5.0}}
    a = traffic.arrivals(mix, 3, 200.0)
    assert a == traffic.arrivals(mix, 3, 200.0) and a == sorted(a)
    assert 850 < len(a) < 1150
    burst = {"arrivals": {"kind": "onoff", "rate_per_s": 2.0, "period_s": 10,
                          "on_s": 2, "on_factor": 4}}
    b = traffic.arrivals(burst, 3, 400.0)
    on = sum(1 for t in b if t % 10 < 2)
    assert 0.4 < on / len(b) < 0.6  # 2 s at 8/s against 8 s at 2/s


def test_tokenizer_model_round_trip():
    ids = [3, 258, 259, 32767, 55555, 63999]
    text = tokens.text_of(ids)
    assert [tokens.id_of(c) for c in text] == ids
    assert tokens.encode_text(text, "completions") == [1] + ids
    chat = tokens.encode_text(text, "chat")
    assert len(chat) == len(ids) + tokens.overhead("chat")
    assert chat[1] == ord("<") + 3  # the template's ASCII goes by byte fallback


def test_end_to_end_arithmetic():
    head = {"window": [100.0, 110.0]}
    times = [101.0 + 0.1 * i for i in range(40)]            # 40 tokens, whole
    recs = [
        {"due": 100.5, "sent": 100.51, "end": 105.0, "ok": True, "cut": False,
         "times": times, "n_out": 40, "max_tokens": 40, "n_prompt": 10},
        {"due": 99.0, "sent": 99.0, "end": 103.0, "ok": True, "cut": False,
         "times": [99.5, 100.5, 101.5], "n_out": 3, "max_tokens": 3, "n_prompt": 10},
        {"due": 108.0, "sent": 108.0, "end": 110.0, "ok": False, "cut": True,
         "times": [109.0, 109.5], "n_out": 2, "max_tokens": 50, "n_prompt": 10},
        {"due": 104.0, "sent": 104.0, "end": 104.1, "ok": False, "cut": False,
         "times": [], "n_out": 0, "max_tokens": 50, "status": 429, "n_prompt": 10},
    ]
    s = e2e.summarize(head, recs)
    assert (s["attempted"], s["failed"]) == (4, 1)
    assert s["out_tokens_in_window"] == 40 + 2 + 2
    assert abs(s["out_tok_s"] - 4.4) < 1e-9
    assert abs(s["ttft_p50_ms"] - 750.0) < 1e-6      # median of 500 and 1000
    assert abs(s["tpot_p50_ms"] - 100.0) < 1e-6 and abs(s["stall_p50_ms"] - 100.0) < 1e-6
    assert abs(s["itl_p50_ms"] - 100.0) < 1e-6
    assert s["n_whole"] == 1 and s["n_completed"] == 1
