"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Drives the continuous-batching engine (:mod:`repro.serve`, DESIGN.md
§9): randomly initializes weights from ``--seed``, optionally as packed
ELP_BSD codes made one float leaf at a time
(:func:`repro.api_schemes.init_packed_lm_params`), stands up a :class:`~repro.serve.ServeEngine` on an elastic
mesh (``runtime/elastic`` picks the largest divisibility-honoring mesh
for the alive devices), and serves a mixed-length staggered request
trace — no padding of short prompts, slots reused the step a request
finishes. Prints per-request outputs, throughput, a latency report
(p50/p99 TTFT, inter-token latency, request time — dispatch-clocked
per-request histograms, DESIGN.md §11), the Table II modeled
energy-per-token, and the straggler monitor's slow-step summary
(``--flash-decode`` turns on the sequence-sharded flash-decoding cache
layout from §Perf).

``--metrics-out PATH`` additionally writes the schema-versioned obs
snapshot to PATH and the per-request span event log (submit → admit →
decode/round → finish) to ``PATH``'s sibling ``*.events.jsonl``;
validate with ``python -m repro.obs --validate PATH``.

Families outside the engine (recurrent / enc-dec / frontend archs) and
``--static`` fall back to the lockstep static batch loop.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api_schemes import init_packed_lm_params
from repro.configs import get_config
from repro.data.pipeline import LmDataset
from repro.models import get_model
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.quantized_params import packed_bytes
from repro.serve import ENGINE_FAMILIES, ServeEngine, ServeSetup, static_generate


def _trace(ds: LmDataset, prompt_len: int, n_requests: int, max_new: int):
    """Deterministic mixed-length request trace with staggered arrivals."""
    base = np.asarray(ds.np_batch(0)["tokens"])
    lens = (max(prompt_len // 4, 4), max(prompt_len // 2, 8), prompt_len)
    news = (max_new, max(max_new // 2, 4), max(max_new // 4, 2))
    reqs, arrivals = [], []
    for i in range(n_requests):
        row = base[i % base.shape[0]]
        reqs.append((row[: lens[i % 3]], news[i % 3]))
        arrivals.append(i // 2)  # two arrivals per engine step
    return reqs, arrivals


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0, help="seed of the random weights")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--quant", default=None, choices=[None, "elp4", "elp8"])
    ap.add_argument("--flash-decode", action="store_true")
    ap.add_argument("--static", action="store_true", help="legacy lockstep batch loop")
    ap.add_argument(
        "--speculative",
        action="store_true",
        help="self-speculative draft/verify serving (DESIGN.md §10): the "
        "--draft-fmt tier (or the ngram table) drafts, the launcher's "
        "serving weights verify and define the output",
    )
    ap.add_argument(
        "--spec-k", type=int, default=7, help="speculative verify width (>= 2)"
    )
    ap.add_argument(
        "--draft-fmt",
        default="elp4",
        choices=["elp4", "elp8", "ngram"],
        help='draft source for --speculative: a packed tier of the same '
        'checkpoint, or "ngram" (token-recycling lookup, no draft forwards)',
    )
    ap.add_argument(
        "--metrics-out",
        default=None,
        metavar="PATH",
        help="write the obs snapshot JSON here (and the span event log "
        "to the sibling *.events.jsonl)",
    )
    ap.add_argument(
        "--profile-dir",
        default=None,
        metavar="DIR",
        help="capture a jax.profiler trace of the first decode dispatches into DIR",
    )
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(args.seed)
    draft_params = None
    spec_draft = "model"
    # packed tiers are made one float leaf at a time: a full-width float
    # tree (qwen3_8b: 16.4 GB in bf16) does not fit one chip, its packed
    # tree does
    if args.quant:
        params = init_packed_lm_params(cfg, key, args.quant)
    else:
        params = get_model(cfg).init_params(cfg, key)
    if args.speculative and args.draft_fmt != "ngram":
        draft_params = init_packed_lm_params(cfg, key, args.draft_fmt)
        print(
            f"speculative serving: {args.draft_fmt} drafts "
            f"({packed_bytes(draft_params) / 1e6:.1f} MB), "
            f"{'packed' if args.quant else 'float'} verifies, K={args.spec_k}"
        )
    elif args.speculative:
        spec_draft = "ngram"
        print(f"speculative serving: ngram drafts, K={args.spec_k}")
    if args.quant:
        print(f"quantized weights: {packed_bytes(params) / 1e6:.1f} MB")

    ds = LmDataset(cfg, seq_len=args.prompt_len, batch=max(args.slots, 4), seed=7)
    max_len = args.prompt_len + args.max_new

    if args.speculative and (
        args.static or cfg.family not in ENGINE_FAMILIES or cfg.frontend_tokens
    ):
        raise SystemExit(
            "--speculative needs the slot engine (transformer families, not "
            "--static): the lockstep loop has no draft/verify path"
        )
    if args.static or cfg.family not in ENGINE_FAMILIES or cfg.frontend_tokens:
        from repro.runtime.elastic import make_mesh

        mesh = make_mesh() if len(jax.devices()) > 1 else None
        npb = ds.np_batch(0)
        batch = {k: jnp.asarray(v) for k, v in npb.items() if k != "labels"}
        setup = ServeSetup(
            cfg=cfg,
            mesh=mesh,
            max_len=max_len,
            batch=batch["tokens"].shape[0],
            flash_decode=args.flash_decode,
            moe_impl="ep" if mesh is not None else "dense",
        )
        toks = static_generate(setup, params, batch, max_new_tokens=args.max_new)
        print("generated (static batch):", np.asarray(toks)[:, :12])
        return

    from repro.obs import ProfileHook, Registry, TraceLog, write_snapshot

    registry = Registry(enabled=True)
    trace_log = None
    if args.metrics_out:
        import os

        trace_log = TraceLog(sink=os.path.splitext(args.metrics_out)[0] + ".events.jsonl")
    profile = ProfileHook(args.profile_dir) if args.profile_dir else None

    engine = ServeEngine(
        cfg,
        params,
        n_slots=args.slots,
        max_len=max_len,
        flash_decode=args.flash_decode,
        draft_params=draft_params,
        spec_k=args.spec_k if args.speculative else 0,
        spec_draft=spec_draft,
        metrics=registry,
        trace=trace_log,
        profile=profile,
    )
    reqs, arrivals = _trace(ds, args.prompt_len, args.requests, args.max_new)
    t0 = time.perf_counter()
    outs = engine.serve(reqs, arrivals=arrivals)
    dt = time.perf_counter() - t0

    for i, ((prompt, _), out) in enumerate(zip(reqs, outs)):
        print(f"req {i}: prompt[{len(prompt)}] -> {out[:12]}")
    st = engine.stats()
    print(
        f"served {len(reqs)} requests / {st['tokens_generated']} tokens in {dt:.2f}s "
        f"({st['tokens_generated'] / dt:.1f} tok/s; {st['decode_steps']} decode steps, "
        f"{st['prefills']} prefills, mesh={st['mesh']})"
    )
    if "speculative" in st:
        sp = st["speculative"]
        print(
            f"speculative: {sp['drafter']} drafter, K={sp['spec_k']}, "
            f"{sp['rounds']} rounds, {sp['tokens_accepted']}/{sp['tokens_drafted']} "
            f"drafted tokens accepted ({sp['acceptance_rate']:.3f})"
        )
    lat = st["latency"]
    print(
        f"latency: TTFT p50 {lat['ttft_p50_s'] * 1e3:.1f} ms / "
        f"p99 {lat['ttft_p99_s'] * 1e3:.1f} ms; "
        f"ITL p50 {lat['itl_p50_s'] * 1e3:.2f} ms / "
        f"p99 {lat['itl_p99_s'] * 1e3:.2f} ms; "
        f"request p50 {lat['request_p50_s'] * 1e3:.1f} ms / "
        f"p99 {lat['request_p99_s'] * 1e3:.1f} ms"
    )
    e = engine.energy
    print(
        f"energy (Table II model): {e['total_nj']:.0f} nJ/token "
        f"({e['fmt']}, {e['macs_per_token'] / 1e6:.1f} M MACs — "
        f"compute {e['compute_nj']:.0f} nJ + weight stream {e['memory_nj']:.0f} nJ); "
        f"total {registry.counter('serve.energy_nj_total').value / 1e6:.2f} mJ"
    )
    sr = st["straggler"]
    print(
        f"straggler: {sr['straggle_events']} slow steps over {sr['steps']} "
        f"(step p50 {sr['p50_s'] * 1e3:.1f} ms / p99 {sr['p99_s'] * 1e3:.1f} ms, "
        f"worst x{sr['worst_ratio']:.2f})"
    )
    if trace_log is not None:
        trace_log.close()
    if args.metrics_out:
        write_snapshot(registry, args.metrics_out)
        print(f"metrics snapshot -> {args.metrics_out}")


if __name__ == "__main__":
    main()
