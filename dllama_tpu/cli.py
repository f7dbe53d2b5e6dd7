"""`dllama` command-line app: inference | generate | chat | worker |
batch | router | serve-pod.

Re-implements the reference app layer (`src/apps/dllama/dllama.cpp` +
`src/app.cpp`) with the same flag surface (`AppArgs::parse`, app.cpp:19-93),
the reference's four modes (dllama.cpp:221-252) plus a beyond-reference
``batch`` mode:

* ``inference`` — benchmark mode: per-token ``G/I/T`` ms line + run
  averages (dllama.cpp:45-93 output contract).
* ``generate``  — stream text for ``--steps`` tokens.
* ``chat``      — REPL with system prompt, chat template, streaming EOS
  detection, KV position persisting across turns (dllama.cpp:111-203).
* ``worker``    — in the reference, a TCP worker process (dllama.cpp:205-
  219).  Within one host the "workers" are mesh devices inside one
  process; across hosts, ``worker`` joins the multi-host process group
  (``--coordinator host:port --nproc N --proc-id K``, parallel/
  distributed.py) and runs the same SPMD program as the root with stdout
  suppressed.
* ``batch``     — beyond reference: decode DISTINCT prompts
  (``--prompts-file``) as one lockstep ragged batch
  (Engine.generate_batch); aggregate tok/s scales with batch while the
  per-step cost stays near one stream's.
* ``router``    — beyond reference: fleet router fronting N dllama-api
  replicas (router/service.py; pure HTTP, no jax in-process).
* ``serve-pod`` — beyond reference: partition the local devices into
  ``--dp`` tensor-parallel serving replicas of ``--workers tpu:N``
  chips each and front them with the fleet router on one public port
  (router/pod.py).

``--workers`` keeps its name but takes ``tpu:N`` (a mesh degree) instead of
host:port pairs — the transport is XLA collectives, not sockets.  ``--sp``/
``--dp`` add sequence-parallel (long context) and data-parallel (batch)
mesh axes, capability the reference does not have.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

from . import quants
from .io import mfile, tfile
from .models.config import ModelConfig
from .models.params import load_params
from .parallel.mesh import parse_workers
from .runtime.engine import Engine, RunStats
from .runtime.stream import drain_generation
from .tokenizer.bpe import Tokenizer
from .tokenizer.chat import ChatItem, ChatTemplate, TokenizerChatStops
from .tokenizer.eos import EosDetector

DTYPES = {"f32": "float32", "bf16": "bfloat16", "f16": "float16"}


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dllama", description=__doc__)
    p.add_argument("mode", choices=["inference", "generate", "chat", "worker",
                                    "batch", "router", "serve-pod"])
    p.add_argument("--model", help="path to .m model file")
    p.add_argument("--tokenizer", help="path to .t tokenizer file")
    p.add_argument("--prompt", default=None)
    p.add_argument("--prompts-file", default=None,
                   help="batch mode: file with one prompt per line; each "
                        "line decodes as its own distinct stream in one "
                        "lockstep batch (beyond-reference capability — the "
                        "reference is batch=1, tasks.cpp:199-210)")
    p.add_argument("--steps", type=int, default=0)
    p.add_argument("--temperature", type=float, default=0.8)  # app.cpp:31
    p.add_argument("--topp", type=float, default=0.9)         # app.cpp:32
    p.add_argument("--seed", type=int, default=None)          # time-based default (app.cpp:33)
    p.add_argument("--weights-float-type", choices=list(quants.FLOAT_TYPE_BY_NAME),
                   default=None, help="required for legacy .m files without a header key")
    p.add_argument("--buffer-float-type", choices=list(DTYPES) + ["q80"], default="bf16",
                   help="compute dtype (the reference's wire/buffer quantization "
                        "analogue); 'q80' is accepted for reference-command parity "
                        "and maps to bf16 (Q80's purpose is wire compression, "
                        "tasks.cpp:124-163 — the 'wire' here is ICI inside the "
                        "XLA program)")
    p.add_argument("--workers", default=None, help="tpu:N mesh degree")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel degree: shards the KV cache's "
                        "sequence axis over the mesh for long context "
                        "(beyond-reference capability; see ops/sp_attention.py)")
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel degree: shards the batch axis over a "
                        "dp mesh axis (beyond-reference capability). In "
                        "batch mode the dp shards carry DISTINCT prompts; "
                        "in the single-prompt modes the dp rows are "
                        "replicas and only stream 0 is printed")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel degree for MoE models: expert "
                        "stacks — dense AND packed Q40 — shard over experts "
                        "instead of replicating (beyond-reference; the "
                        "reference TP-slices all experts everywhere, "
                        "transformer.cpp:299-317; packed path: ops/q40.py "
                        "_sharded_matmul_ep)")
    p.add_argument("--coordinator", default=None,
                   help="multi-host: process-0 host:port for "
                        "jax.distributed.initialize (parallel/distributed.py); "
                        "every process runs the same command with the same "
                        "model flags")
    p.add_argument("--nproc", type=int, default=None,
                   help="multi-host: total process count")
    p.add_argument("--proc-id", type=int, default=None,
                   help="multi-host: this process's id (0 = root)")
    p.add_argument("--program", choices=list(WORKER_PROGRAMS),
                   default="generate",
                   help="worker mode: which root program this worker mirrors "
                        "(multi-host SPMD runs the same program on every process)")
    p.add_argument("--max-seq-len", type=int, default=None)
    p.add_argument("--kv-cache-dtype", choices=list(DTYPES) + ["q8"],
                   default=None,
                   help="cache dtype (default bf16; reference parity is "
                        "f32).  'q8' stores int8 values + per-position "
                        "scales: ~2x less cache HBM traffic/residency, so "
                        "max context per chip nearly doubles "
                        "(beyond-reference)")
    p.add_argument("--chunk", type=int, default=16, help="on-device decode chunk size")
    p.add_argument("--pld", type=int, default=0, metavar="K",
                   help="generate mode, temperature 0: prompt-lookup "
                        "speculative decoding — propose K tokens from the "
                        "latest matching n-gram in the context and verify "
                        "them in ONE forward (beyond-reference; a valid "
                        "greedy stream — bit-identical to plain greedy up "
                        "to argmax near-ties between the T=1 and T=K+1 "
                        "forwards' reduction orders)")
    p.add_argument("--dequantize", action="store_true",
                   help="load Q40 weights as dense bf16 instead of the packed "
                        "fused-kernel path (debugging / numerics comparison)")
    p.add_argument("--warmup", type=int, default=0,
                   help="inference mode: generate this many throwaway tokens "
                        "first (compiles the prefill bucket and decode chunks) "
                        "so the timed stats measure steady state, not XLA "
                        "compilation; 0 = reference parity (it has no compile)")
    p.add_argument("--profile-split", action="store_true",
                   help="inference mode: after the run, trace a few decode steps "
                        "with the XLA profiler and report compute vs collective "
                        "time (the reference's I/T split, SURVEY §5-tracing)")
    p.add_argument("--profile-ops", action="store_true",
                   help="inference mode: like --profile-split but also lists "
                        "the top per-op device times (where did the decode "
                        "step's milliseconds actually go); same xplane trace, "
                        "deeper report")
    p.add_argument("--nthreads", type=int, default=0, help="accepted for reference CLI parity; unused on TPU")
    p.add_argument("--port", type=int, default=9990,
                   help="accepted for reference CLI parity; only the API server "
                        "(python -m dllama_tpu.server.api) listens on it")
    p.add_argument("--batch-slots", type=int, default=0,
                   help="api server: serve /v1/completions list-prompts as one "
                        "lockstep batch with this many slots (a second KV "
                        "cache; weights are shared); also enables the "
                        "continuous-batching slot scheduler for single-"
                        "stream requests (runtime/scheduler.py)")
    p.add_argument("--sched-prefill-chunk", type=int, default=16,
                   help="continuous batching: prompt tokens fed per mixed "
                        "prefill step when a request joins mid-decode; "
                        "smaller chunks bound the extra inter-token latency "
                        "a join adds to running streams")
    p.add_argument("--sched-max-wait-ms", type=float, default=50.0,
                   help="continuous batching: with requests queued for a "
                        "slot, clamp on-device decode bursts so a finishing "
                        "stream frees its slot within about this many "
                        "milliseconds")
    p.add_argument("--sched-max-queue", type=int, default=32,
                   help="continuous batching: max requests waiting for a "
                        "slot (beyond free slots); excess submissions get "
                        "429 + Retry-After")
    p.add_argument("--kv-pages", type=int, default=0,
                   help="slot scheduler: back the slot KV cache with a paged "
                        "pool of this many pages instead of per-slot "
                        "contiguous rows (page 0 is reserved scratch).  "
                        "Pages are allocated per request at admission and "
                        "shared across requests with identical prompt "
                        "prefixes (radix prefix cache), so the pool can be "
                        "sized well below slots x max-seq-len "
                        "(docs/PERF.md).  0 = contiguous (default)")
    p.add_argument("--kv-page-size", type=int, default=16,
                   help="paged KV: tokens per page; prefix sharing works in "
                        "whole pages, so smaller pages share more of a "
                        "common prompt but make longer page tables")
    p.add_argument("--kv-reserve", choices=("full", "optimistic"),
                   default="full",
                   help="paged KV: page reservation policy.  'full' "
                        "reserves every page a request can ever touch at "
                        "admission (exhaustion = queueing, spill never "
                        "engages); 'optimistic' admits with only "
                        "ceil((prompt + --spill-headroom)/page) pages and "
                        "grows slots page-by-page at decode, reclaiming "
                        "through radix eviction and host-RAM spill under "
                        "pressure (docs/PERF.md KV tiering)")
    p.add_argument("--spill-headroom", type=int, default=16,
                   help="optimistic KV reservation: decode tokens of "
                        "slack reserved beyond the prompt at admission "
                        "(and at preempt-resume); larger values grow "
                        "less often, smaller ones admit more "
                        "concurrently")
    p.add_argument("--kv-host-pool-mb", type=float, default=64.0,
                   help="KV tiering: pinned host-RAM budget (MiB) for "
                        "spilled KV pages; a spill that would not fit "
                        "falls back to preempt/park (0 disables "
                        "spilling entirely)")
    p.add_argument("--kv-quant", choices=("off", "int8"), default="off",
                   help="paged KV: store pages quantized int8 with "
                        "per-page scales (~half the pool bytes of bf16); "
                        "attention dequantizes fused at read "
                        "(dispatch ledger codec kv_int8).  Snapshots "
                        "and DLREQ01 hand-off records carry the codec; "
                        "geometry-compatible peers with a different "
                        "codec reject cleanly")
    p.add_argument("--no-prefix-reuse", action="store_true",
                   help="paged KV: disable the radix prefix cache (pages "
                        "are still pooled; nothing is shared or retained "
                        "across requests) — A/B baseline for "
                        "prefix_tokens_reused metrics")
    p.add_argument("--no-sched-overlap", action="store_true",
                   help="slot scheduler: disable the two-deep overlapped "
                        "dispatch pipeline (device-fed pipelined decode "
                        "bursts) and dispatch fully synchronously — debug "
                        "switch and A/B baseline; greedy output is "
                        "byte-identical either way (docs/PERF.md)")
    p.add_argument("--spec", choices=("off", "pld", "draft"), default="off",
                   help="slot scheduler: per-slot speculative decoding "
                        "(runtime/spec.py).  'pld' drafts from a per-slot "
                        "prompt-lookup n-gram index (zero extra model "
                        "cost), 'draft' from a second smaller model "
                        "(--draft-model).  Greedy output stays "
                        "byte-identical to 'off'; sampled (temperature>0) "
                        "requests decode normally (docs/PERF.md)")
    p.add_argument("--spec-k", type=int, default=4,
                   help="speculative decoding: max draft tokens proposed "
                        "per slot per verify window (window width is "
                        "spec-k+1 and rides the compile key, so changing "
                        "it mints one new executable)")
    p.add_argument("--draft-model", default=None,
                   help="--spec draft: path to the draft model (same "
                        "format as the target; loaded like --model onto "
                        "the same mesh with a slot-aligned contiguous KV "
                        "cache)")
    p.add_argument("--no-preempt", action="store_true",
                   help="QoS: disable priority preemption (paged scheduler "
                        "only); admission stays priority-ordered but a "
                        "higher-priority arrival never evicts a running "
                        "lower-priority slot (docs/SERVING.md QoS)")
    p.add_argument("--preempt-age-ms", type=float, default=5000.0,
                   help="QoS: a queued request climbs one priority class "
                        "per this many ms waited, bounding starvation of "
                        "batch traffic behind interactive load (0 = no "
                        "aging; aged rank affects admission order only, "
                        "never eviction)")
    p.add_argument("--preempt-cap", type=int, default=3,
                   help="QoS: max times one request may be preempted and "
                        "parked; past the cap it finishes honestly with "
                        "finish_reason=\"preempted\" and whatever tokens "
                        "it produced")
    p.add_argument("--preempt-spill-dir", default=None,
                   help="QoS: spill parked DLREQ01 records of preempted "
                        "requests to this directory instead of holding "
                        "them in RAM (the parked count stays bounded by "
                        "--sched-max-queue either way)")
    # ---- serving robustness (api server; docs/ROBUSTNESS.md) ----
    p.add_argument("--host", default="0.0.0.0",
                   help="api server: bind address (default 0.0.0.0)")
    p.add_argument("--max-pending", type=int, default=8,
                   help="api server: max requests in flight or queued; "
                        "excess get 429 + Retry-After (bounded admission)")
    p.add_argument("--request-timeout", type=float, default=0.0,
                   help="api server: default per-request deadline in seconds "
                        "(0 = none); requests may lower it with a 'timeout' "
                        "body field.  Expired requests return a truncated "
                        "completion with finish_reason=\"timeout\"")
    p.add_argument("--io-timeout", type=float, default=15.0,
                   help="api server: socket read/write timeout; a client "
                        "stalled sending its body gets 408, one stalled "
                        "reading a stream is treated as disconnected")
    p.add_argument("--drain-grace", type=float, default=30.0,
                   help="api server: on SIGTERM/SIGINT, seconds granted to "
                        "in-flight requests before their deadlines clamp")
    p.add_argument("--step-timeout", type=float, default=None,
                   help="watchdog: seconds a device step may block before "
                        "StepTimeout (default DLLAMA_STEP_TIMEOUT or none); "
                        "turns a silently hung device into a diagnosable "
                        "error naming the step, position and mesh")
    # ---- artifact integrity / state recovery (docs/ROBUSTNESS.md) ----
    p.add_argument("--verify-weights", action="store_true",
                   help="verify each tensor's crc32 against the model's "
                        "sidecar checksum manifest (<model>.m.sum, written "
                        "by tools/checksum_model.py) on first read; the "
                        "header digest is always verified when the manifest "
                        "exists.  Fails fast with ArtifactError on any "
                        "corruption instead of decoding garbage")
    p.add_argument("--numeric-checks", action="store_true",
                   help="check host-fetched logits for NaN/Inf every step "
                        "and raise NumericFault (step, pos) instead of "
                        "emitting garbage tokens (default "
                        "DLLAMA_NUMERIC_CHECKS)")
    p.add_argument("--snapshot-dir", default=None,
                   help="api server: directory for engine-state snapshots; "
                        "on SIGTERM drain the KV cache/position/RNG persist "
                        "here and the next boot warm-starts from it "
                        "(validated: a corrupt or mismatched snapshot "
                        "cold-starts with a logged reason)")
    p.add_argument("--handoff", action="store_true",
                   help="api server: on SIGTERM drain, export each in-flight "
                        "scheduler request as a per-request DLREQ01 hand-off "
                        "record (KV pages + decode state) fetchable via "
                        "/admin/export/<rid>, and accept records from peers "
                        "at /admin/import — the fleet router migrates "
                        "requests between replicas with these during a "
                        "rolling restart (docs/SERVING.md).  Requires the "
                        "paged scheduler (--batch-slots + --kv-pages)")
    # ---- fleet router (router/ package; docs/SERVING.md) ----
    p.add_argument("--backends", default=None,
                   help="router mode: comma-separated replica addresses "
                        "(host:port,...) fronted by this router; each must "
                        "be a dllama-api server")
    p.add_argument("--probe-interval", type=float, default=2.0,
                   help="router mode: seconds between /health probes of "
                        "each backend")
    p.add_argument("--eject-after", type=int, default=3,
                   help="router mode: consecutive probe/dispatch failures "
                        "before a backend is ejected from dispatch")
    p.add_argument("--readmit-after", type=int, default=2,
                   help="router mode: consecutive successful probes before "
                        "an ejected backend is re-admitted (hysteresis: "
                        "one lucky probe does not un-eject)")
    p.add_argument("--router-retries", type=int, default=2,
                   help="router mode: max re-dispatches of a request to "
                        "another backend when one fails before any "
                        "response bytes were forwarded")
    p.add_argument("--upstream-timeout", type=float, default=120.0,
                   help="router mode: socket timeout per upstream request "
                        "(connect + per-read); a backend silent past this "
                        "is treated as failed")
    # ---- crash tolerance (router resume + pod supervisor;
    #      docs/ROBUSTNESS.md) ----
    p.add_argument("--handoff-ttl", type=float, default=0.0,
                   help="api server: seconds an exported DLREQ01 hand-off "
                        "record waits unclaimed before it is garbage-"
                        "collected (dllama_handoff_expired_total counts "
                        "them); 0 = keep until claimed.  Bounds drain "
                        "time when the router never comes to collect")
    p.add_argument("--stall-timeout", type=float, default=0.0,
                   help="router mode: seconds an open upstream stream may "
                        "go silent before the replica is treated as dead "
                        "(force-ejected) and the stream resumed elsewhere; "
                        "catches wedged-but-connected replicas (SIGSTOP, "
                        "device hang) that a connect timeout never sees.  "
                        "Also bounds time-to-first-token, so set it above "
                        "worst-case queue + prefill + compile.  0 = off")
    p.add_argument("--checkpoint-interval", type=float, default=0.0,
                   help="router mode: seconds between proactive DLREQ01 "
                        "checkpoints of each in-flight greedy stream "
                        "(GET /admin/checkpoint/<rid>); a crashed "
                        "replica's streams then resume from the latest "
                        "checkpoint instead of re-prefilling the whole "
                        "prompt.  Requires replicas running --handoff. "
                        "0 = off (resume falls back to deterministic "
                        "re-run)")
    p.add_argument("--resume-policy", choices=["auto", "never"],
                   default="auto",
                   help="router mode: default mid-stream crash behavior — "
                        "auto resumes greedy streams on a peer (byte-"
                        "identical; sampled streams always get the honest "
                        "replica_lost), never disables resume fleet-wide. "
                        "Per-request override: \"resume_policy\" body "
                        "field")
    p.add_argument("--supervise", action="store_true",
                   help="serve-pod: run each replica as a child PROCESS "
                        "under a supervisor that respawns it on crash "
                        "(same port + device set, warm --snapshot-dir "
                        "restore) and SIGKILLs+respawns it when /health "
                        "hangs; crash-looping replicas are quarantined "
                        "(--respawn-max/--respawn-window)")
    p.add_argument("--respawn-max", type=int, default=5,
                   help="serve-pod --supervise: deaths tolerated inside "
                        "--respawn-window before a replica is quarantined "
                        "instead of respawned")
    p.add_argument("--respawn-window", type=float, default=30.0,
                   help="serve-pod --supervise: sliding window (seconds) "
                        "for the crash-loop counter")
    # ---- elastic pod (router/elastic.py; docs/SERVING.md) ----
    p.add_argument("--elastic", action="store_true",
                   help="serve-pod --supervise: load-driven autoscaling "
                        "and live tp reshape — a control loop samples "
                        "fleet /health signals and spawns, drains, or "
                        "reshapes replicas within the --pod-devices "
                        "budget.  Needs --handoff + --batch-slots/"
                        "--kv-pages (in-flight requests migrate over "
                        "the hand-off wire)")
    p.add_argument("--pod-devices", type=int, default=0,
                   help="serve-pod --elastic: total device budget the "
                        "pod may partition into replicas (default "
                        "dp × tp — no headroom to grow)")
    p.add_argument("--min-replicas", type=int, default=1,
                   help="serve-pod --elastic: scale-down floor")
    p.add_argument("--max-replicas", type=int, default=0,
                   help="serve-pod --elastic: scale-up ceiling "
                        "(default: the boot dp)")
    p.add_argument("--elastic-interval", type=float, default=2.0,
                   help="serve-pod --elastic: seconds between control-"
                        "loop ticks (one fleet sample per tick)")
    p.add_argument("--elastic-window", type=int, default=5,
                   help="serve-pod --elastic: samples in the sliding "
                        "window; EVERY sample must agree before a "
                        "policy action fires (sustained signal, not a "
                        "spike)")
    p.add_argument("--elastic-cooldown", type=float, default=30.0,
                   help="serve-pod --elastic: seconds after any "
                        "topology action before the policy may act "
                        "again (the window also refills from empty)")
    p.add_argument("--scale-up-util", type=float, default=0.85,
                   help="serve-pod --elastic: sustained fleet slot "
                        "utilization at or above this adds a replica")
    p.add_argument("--scale-down-util", type=float, default=0.15,
                   help="serve-pod --elastic: sustained utilization at "
                        "or below this (with an empty queue) retires "
                        "the most-idle replica")
    p.add_argument("--scale-up-queue", type=float, default=2.0,
                   help="serve-pod --elastic: sustained queued requests "
                        "per replica at or above this also triggers "
                        "scale-up")
    p.add_argument("--reshape-kv-low", type=float, default=0.08,
                   help="serve-pod --elastic: sustained effective-free "
                        "KV fraction at or below this reshapes to "
                        "fewer, wider replicas (tp×2) — the long-"
                        "context answer")
    # ---- observability (docs/OBSERVABILITY.md) ----
    p.add_argument("--log-format", choices=["human", "json"], default=None,
                   help="log output format: human-readable lines or JSON "
                        "lines (one object per record, grep-able by "
                        "request_id).  Default: DLLAMA_LOG env, else human")
    p.add_argument("--log-level", default=None,
                   choices=["debug", "info", "warning", "error"],
                   help="log verbosity for the dllama logger tree "
                        "(default: DLLAMA_LOG env, else info)")
    p.add_argument("--trace-buffer", type=int, default=None,
                   help="span ring capacity for /debug/trace (default "
                        "DLLAMA_TRACE_BUFFER, else 8192)")
    p.add_argument("--flight-buffer", type=int, default=None,
                   help="flight-recorder ring capacity for /debug/requests "
                        "(default DLLAMA_FLIGHT_BUFFER, else 512)")
    p.add_argument("--event-buffer", type=int, default=None,
                   help="event-journal ring capacity for /debug/events "
                        "(default DLLAMA_EVENT_BUFFER, else 2048)")
    p.add_argument("--event-log", default=None, metavar="PATH",
                   help="also append every event-journal record as a JSONL "
                        "line to PATH (append mode — restarts extend), so "
                        "spawn/quarantine/scale/reshape incidents survive "
                        "the process that emitted them")
    p.add_argument("--slo", default=None, metavar="SPEC",
                   help="declarative latency/error objectives, e.g. "
                        "'ttft_p95=1500ms,itl_p99=120ms,error_rate=0.5%%'. "
                        "Burn rates over rolling windows (DLLAMA_SLO_WINDOWS, "
                        "default 5m,1h) feed slo_burn_rate gauges and the "
                        "/health verdict.  Default: DLLAMA_SLO env")
    return p


def load_stack(args, batch: int | None = None) -> tuple[Engine, Tokenizer]:
    import jax.numpy as jnp
    if not args.model or not args.tokenizer:
        raise SystemExit("--model and --tokenizer are required for this mode")
    wft = quants.FLOAT_TYPE_BY_NAME[args.weights_float_type] if args.weights_float_type else None
    mf = mfile.MFile(args.model, weights_ftype=wft,
                     verify=getattr(args, "verify_weights", False))
    bft = args.buffer_float_type
    if bft == "q80":
        print("💡 bufferFloatType q80 → bf16 (activations stay on-chip; Q80's "
              "wire compression has no wire to compress here)")
        bft = "bf16"
    dtype = jnp.dtype(DTYPES[bft])
    cfg = ModelConfig.from_spec(mf.spec, dtype=dtype)
    print(f"💡 arch: {mf.spec.arch_name}")
    print(f"💡 dim: {cfg.dim}\n💡 nLayers: {cfg.n_layers}\n💡 nHeads: {cfg.n_heads}")
    print(f"💡 nKvHeads: {cfg.n_kv_heads}\n💡 vocabSize: {cfg.vocab_size}\n💡 seqLen: {cfg.seq_len}")
    mesh = parse_workers(args.workers, sp=args.sp, dp=args.dp, ep=args.ep)
    axes = {k: v for k, v in mesh.shape.items() if v > 1} or {"tp": 1}
    print("💡 mesh: " + " ".join(f"{k}={v}" for k, v in axes.items()))
    # fused qkv/w13 is the single-chip fast layout; under tp>1 the unfused
    # per-tensor layout shards cleanly (see load_params)
    cfg, params = load_params(mf, cfg, dtype=dtype,
                              keep_quantized=not args.dequantize,
                              fuse=mesh.shape.get("tp", 1) == 1)
    kv_dtype = ("q8" if args.kv_cache_dtype == "q8"
                else jnp.dtype(DTYPES[args.kv_cache_dtype])
                if args.kv_cache_dtype else None)
    engine = Engine(cfg, params, mesh=mesh, seq_len=args.max_seq_len,
                    kv_dtype=kv_dtype, batch=batch or max(args.dp, 1),
                    step_timeout=getattr(args, "step_timeout", None),
                    # flag turns checks ON; absent → None keeps the
                    # DLLAMA_NUMERIC_CHECKS env default
                    numeric_checks=(True if getattr(args, "numeric_checks",
                                                    False) else None))
    tok = Tokenizer(tfile.read_tfile(args.tokenizer))
    if tok.vocab_size != cfg.vocab_size:
        raise SystemExit("tokenizer is incompatible with model (vocab size mismatch)")
    return engine, tok


def load_draft_engine(args, target: Engine) -> Engine:
    """Load ``--draft-model`` as a second, smaller Engine on the target's
    mesh for ``--spec draft`` (runtime/spec.py DraftModelProposer): same
    slot count and context as the target, contiguous slot-aligned KV (the
    draft pool is tiny, paging would only add indirection).  Weights are
    a second full load; the KV cache is the only per-slot state."""
    import jax.numpy as jnp
    if not args.draft_model:
        raise SystemExit("--spec draft needs --draft-model")
    wft = (quants.FLOAT_TYPE_BY_NAME[args.weights_float_type]
           if args.weights_float_type else None)
    mf = mfile.MFile(args.draft_model, weights_ftype=wft,
                     verify=getattr(args, "verify_weights", False))
    bft = args.buffer_float_type
    dtype = jnp.dtype(DTYPES["bf16" if bft == "q80" else bft])
    cfg = ModelConfig.from_spec(mf.spec, dtype=dtype)
    if cfg.vocab_size != target.cfg.vocab_size:
        raise SystemExit("--draft-model vocab size differs from the "
                         "target's (drafted ids must be target token ids)")
    print(f"💡 draft arch: {mf.spec.arch_name} "
          f"({cfg.n_layers} layers, dim {cfg.dim})")
    cfg, params = load_params(mf, cfg, dtype=dtype,
                              keep_quantized=not args.dequantize,
                              fuse=target.mesh.shape.get("tp", 1) == 1)
    return Engine(cfg, params, mesh=target.mesh, seq_len=target.seq_len,
                  batch=target.batch,
                  step_timeout=getattr(args, "step_timeout", None))


def _seed(args) -> int:
    return args.seed if args.seed is not None else int(time.time())


def _print_slo_summary(args) -> None:
    """End-of-run SLO verdict beside the dispatch summary (obs/slo.py);
    silent unless the operator declared objectives (main() validates the
    spec up front and stashes the engine)."""
    slo = getattr(args, "_slo_engine", None)
    if slo is not None:
        print(slo.summary_line())


def _encode_prompt(engine, tok, prompt: str) -> list[int]:
    """Prompt encoding with the reference's BOS rule (ModelConfig.add_bos:
    Grok-1 prompts get no BOS, dllama.cpp:27)."""
    return tok.encode(prompt, add_bos=engine.cfg.add_bos)


def cmd_inference(args) -> None:
    """Benchmark mode (dllama.cpp:45-93): prints per-token G/I/T."""
    engine, tok = load_stack(args)
    prompt = args.prompt or "Hello world"
    ids = _encode_prompt(engine, tok, prompt)
    steps = args.steps or 64
    if args.chunk > 1:
        print(f"💡 decode runs on-device in chunks of {args.chunk}; G/I/T "
              "lines within a chunk are that chunk's per-token averages")
    if args.warmup > 0:
        t0 = time.perf_counter()
        for _ in engine.generate_stream(
                ids, len(ids) + args.warmup, temperature=args.temperature,
                topp=args.topp, seed=_seed(args), chunk=args.chunk):
            pass
        engine.reset()
        print(f"💡 warmup: {args.warmup} tokens in "
              f"{time.perf_counter() - t0:.1f}s (compile excluded from stats)")
    stats = RunStats()
    pieces = []
    prev = tok.bos_id
    for token, st in engine.generate_stream(
            ids, steps + len(ids), temperature=args.temperature, topp=args.topp,
            seed=_seed(args), chunk=args.chunk):
        piece = tok.decode_piece(prev, token).decode("utf-8", errors="replace")
        prev = token
        if st.generation_ms > 0:
            stats.add(st)
        print(f"🔶 G {st.generation_ms:7.2f} ms I {st.inference_ms:7.2f} ms "
              f"T {st.transfer_ms:6.2f} ms S {st.sent_bytes / 1024:6.1f} kB "
              f"R {st.recv_bytes / 1024:6.1f} kB | {piece!r}")
        pieces.append(piece)
    print(f"Generated tokens:    {len(stats.tokens)}")
    print(f"Avg tokens / second: {stats.tokens_per_second:.2f}")
    print(f"Avg generation time: {stats.avg_generation_ms:.2f} ms")
    print(f"Avg inference time:  {stats.avg_inference_ms:.2f} ms")
    print(f"Avg transfer time:   {stats.avg_transfer_ms:.2f} ms")
    print(f"Avg sent / recv:     {stats.avg_sent_bytes / 1024:.1f} kB / "
          f"{stats.avg_recv_bytes / 1024:.1f} kB")
    # kernel-dispatch ledger (obs/dispatch.py): which matmul paths this run
    # actually took, and loudly whether anything degraded — a benchmark
    # number from an XLA-dequant fallback must not read as a clean result
    from .obs import dispatch as obs_dispatch
    print(obs_dispatch.summary_line())
    coll = obs_dispatch.collective_line()
    if coll:
        print(coll)
    _print_slo_summary(args)
    if args.profile_split or args.profile_ops:
        from .runtime.profiling import summarize_split, top_ops, \
            traced_op_times
        if engine.pos + 4 > engine.seq_len:
            engine.reset()
            engine.prefill(ids)
        last = ids[-1]
        n_steps = 3
        times = traced_op_times(lambda: engine.decode_one(last), steps=n_steps)
        if times is None:
            print("Profiled split:      unavailable (xplane tooling missing)")
        else:
            sp = summarize_split(times, n_steps)
            n_dev = engine.mesh.size
            print(f"Profiled decode step (mesh sum / {n_dev} devices): "
                  f"compute {sp['compute_ms']:.2f} ms, "
                  f"collectives {sp['collective_ms']:.2f} ms "
                  f"({sp['collective_pct']:.1f}%)")
            n_top = 10 if args.profile_ops else 5
            for op, ms in top_ops(times, n_top, n_steps):
                print(f"  top op {ms:8.2f} ms  {op}")


def cmd_generate(args) -> None:
    engine, tok = load_stack(args)
    if args.prompt is None:
        raise SystemExit("generate mode requires --prompt")
    ids = _encode_prompt(engine, tok, args.prompt)
    steps = args.steps or engine.seq_len
    prev = tok.bos_id
    eos = (tok.eos_id,) if tok.eos_id >= 0 else ()
    if args.pld > 0:
        if args.temperature != 0:
            raise SystemExit("--pld is greedy-only; set --temperature 0")
        if args.dp > 1 or args.sp > 1:
            raise SystemExit("--pld is single-stream; drop --dp/--sp "
                             "(tp/ep meshes are fine)")
        for token in engine.generate_pld_stream(ids, steps, k=args.pld,
                                                eos_ids=eos):
            sys.stdout.write(tok.decode_piece(prev, token)
                             .decode("utf-8", errors="replace"))
            sys.stdout.flush()  # text appears per verify window, not at end
            prev = token
        print()
        return
    for token, _ in engine.generate_stream(
            ids, steps, temperature=args.temperature, topp=args.topp,
            seed=_seed(args), eos_ids=eos, chunk=args.chunk):
        sys.stdout.write(tok.decode_piece(prev, token).decode("utf-8", errors="replace"))
        sys.stdout.flush()
        prev = token
    print()


def cmd_batch(args) -> None:
    """Batched generation of DISTINCT prompts in one lockstep decode
    (beyond reference — the reference fixes batch=1, tasks.cpp:199-210).

    Prompts come from ``--prompts-file`` (one per line) or a single
    ``--prompt``.  Each stream's output is printed under its own header
    after the batch finishes; the summary line reports aggregate batched
    throughput — the point of batching: the decode matmuls amortize one
    weight read over all rows, so tokens/second scales with batch while
    ms/token stays near the single-stream cost.
    """
    if args.prompts_file:
        with open(args.prompts_file, "r", encoding="utf-8") as f:
            prompts = [ln.rstrip("\r\n") for ln in f if ln.strip()]
    elif args.prompt is not None:
        prompts = [args.prompt]
    else:
        raise SystemExit("batch mode requires --prompts-file or --prompt")
    if args.dp > 1 and len(prompts) % args.dp:
        raise SystemExit(f"{len(prompts)} prompts do not shard over dp={args.dp}")
    engine, tok = load_stack(args, batch=len(prompts))
    id_lists = [_encode_prompt(engine, tok, p) for p in prompts]
    steps = args.steps or engine.seq_len
    eos = (tok.eos_id,) if tok.eos_id >= 0 else ()
    t0 = time.perf_counter()
    outs = engine.generate_batch(id_lists, steps,
                                 temperature=args.temperature, topp=args.topp,
                                 seed=_seed(args), eos_ids=eos, chunk=args.chunk)
    dt = time.perf_counter() - t0
    generated = sum(len(o) - len(p) for o, p in zip(outs, id_lists))
    for r, o in enumerate(outs):
        print(f"▶ stream {r}")
        print(tok.decode(o))
    print(f"Generated tokens:    {generated} over {len(prompts)} streams")
    if dt > 0:
        print(f"Batched throughput:  {generated / dt:.2f} tok/s")
    from .obs import dispatch as obs_dispatch
    print(obs_dispatch.summary_line())
    coll = obs_dispatch.collective_line()
    if coll:
        print(coll)
    _print_slo_summary(args)


def cmd_chat(args) -> None:
    """Multi-turn REPL (dllama.cpp:111-203): one KV cache per conversation."""
    engine, tok = load_stack(args)
    stops = TokenizerChatStops(tok)
    template = ChatTemplate(tok.chat_template, tok.vocab[tok.chat_eos_id].decode("utf-8", "replace"))
    eos_detector = EosDetector(tok.chat_eos_id, stops.stops,
                               padding_left=2, padding_right=2)  # dllama.cpp:198-199

    print("💻 System prompt (optional): ", end="", flush=True)
    system = sys.stdin.readline().strip()
    first = True
    # one sampler stream per REPL session (app.cpp:33 seeds one Sampler per
    # process): the seed is resolved ONCE here — even unset --seed — and
    # later turns continue the stream rather than re-seeding from the wall
    # clock every turn (VERDICT r04 Weak #6)
    session_seed: int | None = _seed(args)
    while True:
        print("\n👱 User\n> ", end="", flush=True)
        user = sys.stdin.readline()
        if not user:
            break
        user = user.strip()
        if not user:
            continue
        items = []
        if first and system:
            items.append(ChatItem("system", system))
        items.append(ChatItem("user", user))
        first = False
        text = template.generate(items, True)
        ids = tok.encode(text, add_bos=engine.pos == 0)
        if engine.pos + len(ids) + 2 >= engine.seq_len:
            print("🚫 context window is full")
            break
        print("\n🤖 Assistant")
        eos_detector.clear()
        prompt_end = engine.pos + len(ids)
        stream = engine.generate_stream(
            ids, engine.seq_len - engine.pos, temperature=args.temperature,
            topp=args.topp, seed=session_seed, chunk=args.chunk,
            eos_ids=(tok.chat_eos_id,))
        session_seed = None  # continue the session stream on later turns

        def emit(delta):
            sys.stdout.write(delta)
            sys.stdout.flush()

        drain_generation(engine, tok, eos_detector, stream, len(ids),
                         prompt_end, emit)
        print()


def cmd_worker(args) -> None:
    """Join a multi-host run as one SPMD process (reference: the TCP worker
    that executes the same task list as root, dllama.cpp:205-219 +
    Worker::work tasks.cpp:230-256).

    Requires process coordinates (--coordinator/--nproc/--proc-id or the
    DLLAMA_* env vars) and the same model flags as the root: every process
    executes the same XLA programs; only process 0 owns stdout.  Within a
    single host no worker processes exist at all — the mesh devices are the
    workers — so without coordinates this mode just explains the mapping.
    """
    from .parallel.distributed import distributed_env

    if not args.coordinator and distributed_env() is None:
        print("On this framework the reference's worker processes are TPU mesh devices\n"
              "inside one program: run the root command with --workers tpu:N instead.\n"
              "For MULTI-HOST runs (e.g. a v5e-16/32 pod slice), start this mode on\n"
              "every host with --coordinator host:port --nproc N --proc-id K and the\n"
              "same --model/--tokenizer/--prompt flags; process 0 prints, the rest\n"
              "compute. (reference: dllama.cpp:205-219 TCP worker; transport here is\n"
              "XLA collectives over ICI/DCN — see dllama_tpu/parallel/distributed.py)")
        return
    # init happened in main(); suppress stdout on non-root processes and run
    # the mirrored program
    from .parallel.distributed import is_output_process

    if not is_output_process():
        import os
        sys.stdout = open(os.devnull, "w")
    WORKER_PROGRAMS[args.program](args)


def cmd_router(args) -> None:
    """Fleet router: front N dllama-api replicas (router/ package; no
    model or jax in this process — it only proxies HTTP)."""
    from .router.service import main as router_main
    router_main(args)


def cmd_serve_pod(args) -> None:
    """Pod-slice serving: partition the local devices into ``--dp``
    tensor-parallel replicas of ``--workers tpu:N`` chips each, serve
    the OpenAI surface per replica, and front them with the fleet
    router on ``--port`` (router/pod.py)."""
    from .router.pod import main as pod_main
    pod_main(args)


# One table drives the --program choices AND the worker dispatch, so a
# new mirrored program cannot be added to one and missed in the other
# (chat stays out: interactive, single-host only).
WORKER_PROGRAMS = {"generate": cmd_generate, "inference": cmd_inference,
                   "batch": cmd_batch}


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    from .hostenv import configure_compile_cache
    configure_compile_cache()
    from .obs.log import configure as configure_logging
    configure_logging(args.log_format, args.log_level)
    from .obs import events as obs_events, flight as obs_flight, \
        trace as obs_trace
    obs_trace.configure(args.trace_buffer)
    obs_flight.configure(args.flight_buffer)
    obs_events.configure(args.event_buffer, args.event_log)
    # validate --slo up front (a bad spec must not surface only after a
    # long run); the engine is consulted again by _print_slo_summary
    spec = args.slo or os.environ.get("DLLAMA_SLO", "")
    if spec:
        from .obs.slo import SloEngine
        try:
            args._slo_engine = SloEngine.from_spec(spec)
        except ValueError as e:
            raise SystemExit(f"--slo: {e}")
    from .parallel.distributed import distributed_env, init_distributed
    if args.coordinator or distributed_env() is not None:
        init_distributed(args.coordinator, args.nproc, args.proc_id)
    {"inference": cmd_inference, "generate": cmd_generate,
     "chat": cmd_chat, "worker": cmd_worker, "batch": cmd_batch,
     "router": cmd_router, "serve-pod": cmd_serve_pod}[args.mode](args)


if __name__ == "__main__":
    main()
