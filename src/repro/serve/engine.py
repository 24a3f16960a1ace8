"""Continuous-batching serving engine on sharded packed weights.

One engine (DESIGN.md §9) replaces the old split between
``runtime/serve_loop.py`` (static padded batches), ``launch/serve.py``'s
ad-hoc driver, and ``QuantizedModel.generate``: requests are admitted
into SLOTS of one persistent sharded KV cache, each slot tracks its own
position, and a single jitted decode step advances every live slot at
once. A finished request's slot is immediately reusable — no
re-prefill of live slots, no padding of short prompts to the batch
maximum.

The cache itself comes in two layouts. ``kv_cache="dense"`` is the
classic ``[L, n_slots, max_len, KV, hd]`` stripe-per-slot tensor.
``kv_cache="paged"`` (DESIGN.md §12) replaces it with a pool of
fixed-size pages addressed through a host-authoritative per-slot page
table (:class:`repro.serve.paging.PageTable`): K/V is optionally
quantized on write to int8 against static per-(layer, head) scales
calibrated by :func:`repro.calib.calibrate_kv_cache`, and admissions
whose prompt prefix exactly matches an indexed page chain reference
those pages copy-on-write-style (refcounted, freed when the last
reader finishes) — a shared system prompt is prefilled once, and only
its suffix per request.

Correctness invariants (tested in ``tests/test_serve_engine.py`` and
``tests/test_paging.py``):

  * **slot isolation** — decode-step cache writes are per-row
    (``models/transformer._cache_set`` with a vector position, or
    ``_cache_set_paged`` routing each row through its own page-table
    row): slot ``b`` writes only its own pages/row, at its own
    position;
  * **mask-past-pos** — attention reads ``kpos <= pos[slot]``, so a
    reused slot's stale entries from the previous occupant are never
    attended: every position ``<= pos`` has been written by the current
    request (prefill covers ``[0, S)``, each decode writes its own
    position before attending to it). The paged gather reproduces the
    dense logical view position-for-position, so the same argument
    covers page reuse — and shared prefix pages hold only positions
    strictly below every sharer's write positions, so they are
    immutable while referenced;
  * **token parity** — greedy continuous output is token-identical to
    per-request static generation: per-row math is independent of what
    the other slots are doing, masked positions contribute exactly zero
    to the softmax, and the admission prefill runs at the request's
    exact prompt length. The quantized paged engine is token-identical
    to the dense static-int8 reference
    (``static_generate(kv_scales=...)``): same codes, same scales,
    paging changes addressing only.

Weights: a packed tree (``PackedWeight`` leaves) is consumed directly by
the jitted decode step — codes enter the graph as uint8 and decode
inside the ELP_BSD matmul path (the fused Pallas kernel on single-device
TPU, the XLA-fused dequant under pjit), so HBM moves code bytes, never a
materialized full-precision weight tree. Sharding: ``codes`` follow the
weight's own rule and per-channel ``sf`` follows the sharded out-dim
(``runtime/sharding.py``), so the packed tree drops onto the mesh the
float tree would use.

Startup wires ``runtime/elastic``: with ``mesh="auto"`` the engine picks
the largest divisibility-honoring mesh for the alive devices
(:func:`repro.runtime.elastic.make_mesh`) and lays the weights out with
:func:`repro.runtime.elastic.reshard`. Each decode step's wall-clock
feeds a :class:`repro.runtime.straggler.StragglerMonitor`;
``stats()["straggler"]`` surfaces the slow-step report.

Observability (DESIGN.md §11): pass ``metrics=Registry(enabled=True)``
and the engine records per-request time-to-first-token and inter-token
latency histograms, queue-depth/slot-occupancy gauges, speculative
round-width and acceptance distributions, and modeled Table II energy
per emitted token (``core/energy``) — all host-side, at the sync points
the loop already pays for (the decode loop stays device-resident).
``trace=TraceLog(...)`` additionally logs the per-request span events
(submit → admit/prefill → decode/round → finish) as JSONL, and
``profile=ProfileHook(dir, n)`` captures a ``jax.profiler`` trace
around the first ``n`` decode dispatches. All three default to off and
cost nothing when off: the disabled registry's instruments are no-ops.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.energy import lm_token_energy
from repro.models import ModelApi, get_model
from repro.models.context import ParallelCtx
from repro.obs.metrics import NULL_REGISTRY, Registry
from repro.obs.trace import ProfileHook, TraceLog, span
from repro.runtime import sharding as shr
from repro.runtime.straggler import StragglerMonitor
from repro.serve.scheduler import Request, SlotScheduler

Array = jax.Array

# Families the slot engine drives. The engine needs the transformer
# cache contract ([L, B, S, KV, hd] dicts, positional RoPE) and a
# token-only prompt; recurrent/enc-dec families — and vlm/audio
# requests carrying frontend embeddings — keep the static path
# (:func:`static_generate`).
ENGINE_FAMILIES = ("dense", "moe", "vlm")


@dataclasses.dataclass(frozen=True)
class ServeSetup:
    """Static serving configuration (mesh, cache geometry, layout knobs)."""

    cfg: ArchConfig
    mesh: Mesh | None
    max_len: int
    batch: int
    moe_impl: str = "ep"
    flash_decode: bool = False
    # paged KV cache geometry (DESIGN.md §12): page_size=0 keeps the
    # dense [L, B, max_len, KV, hd] layout; kv_bits=8 stores int8 codes
    # against static per-(layer, head) scales.
    page_size: int = 0
    kv_bits: int = 0

    def pctx(self) -> ParallelCtx | None:
        if self.mesh is None:
            return None
        return ParallelCtx(
            mesh=self.mesh,
            batch_axes=shr.batch_axes(self.mesh),
            model_axis="model",
            moe_impl=self.moe_impl,
            flash_decode=self.flash_decode,
        )


# ---------------------------------------------------------------------------
# Jitted step builders
# ---------------------------------------------------------------------------
def _place(params: Any, mesh: Mesh) -> Any:
    """``params`` laid out on ``mesh`` by the sharding rules, each packed
    weight marked with its split (:func:`repro.runtime.sharding.mark_splits`).
    Arrays already in their shardings stay where they are."""
    from repro.runtime.elastic import reshard

    specs = shr.param_specs(jax.eval_shape(lambda: params), mesh)
    return shr.mark_splits(reshard(params, mesh, specs), specs, mesh)


def _abstract_params(setup: ServeSetup, api: ModelApi, aparams):
    """Abstract tree the shardings are derived from.

    ``aparams=None`` falls back to the float init tree — callers serving
    a PACKED tree must pass its own abstract shape (the packed pytree
    has a different structure, and its specs come from the
    PackedWeight-aware rules in ``runtime/sharding.py``)."""
    if aparams is not None:
        return aparams
    return jax.eval_shape(lambda: api.init_params(setup.cfg, jax.random.PRNGKey(0)))


def _abstract_cache(setup: ServeSetup, api: ModelApi):
    """Abstract cache tree for the setup's layout (DESIGN.md §9/§12).

    ``page_size`` selects the paged pool + page-table layout; with
    ``kv_bits`` the pool holds int8 codes and the tree carries
    placeholder ``[L, KV]`` static scales (only shapes matter here — the
    real calibrated scales live in the engine's cache). ``kv_bits``
    without ``page_size`` is the dense static-int8 layout
    (:func:`static_generate`'s quantized reference path).
    """
    cfg = setup.cfg
    if setup.page_size or setup.kv_bits:
        from repro.models import transformer

        n_layers = cfg.n_dec_layers or cfg.n_layers
        scales = None
        if setup.kv_bits:
            s = jnp.ones((n_layers, cfg.n_kv_heads), jnp.float32)
            scales = (s, s)
        if setup.page_size:
            return jax.eval_shape(
                lambda: transformer.init_paged_cache(
                    cfg,
                    setup.batch,
                    setup.max_len,
                    page_size=setup.page_size,
                    kv_scales=scales,
                )
            )
        return jax.eval_shape(
            lambda: transformer.init_cache(
                cfg, setup.batch, setup.max_len, kv_scales=scales
            )
        )
    return jax.eval_shape(lambda: api.init_cache(cfg, setup.batch, setup.max_len))


def _jit_step(
    fn,
    setup: ServeSetup,
    api: ModelApi,
    aparams,
    ins: tuple,
    outs: tuple,
    *,
    static_argnums=(),
    donate_one_chip=False,
):
    """``jax.jit(fn)`` placed for ``setup``: the one place a serve step's
    shardings and donation are decided.

    ``ins`` and ``outs`` name what each argument (static ones left out)
    and output is: ``"params"``, ``"tokens"`` (``[batch, ...]``),
    ``"cache"``, ``"state"`` (the cache, and for enc-dec families a
    batch-sharded ``enc_out``), ``"replicated"``, or None (unplaced).
    On one device nothing is placed and only a ``donate_one_chip`` step
    donates its cache; on a mesh the specs come from the sharding rules
    and every step donates its cache, which it returns in the same
    sharding.
    """
    donate = (ins.index("cache"),)
    if setup.mesh is None:
        return jax.jit(
            fn, static_argnums=static_argnums, donate_argnums=donate if donate_one_chip else ()
        )
    mesh = setup.mesh
    pspecs = shr.param_specs(_abstract_params(setup, api, aparams), mesh)
    cspecs = shr.cache_specs_tree(_abstract_cache(setup, api), mesh, prefer_seq=setup.flash_decode)
    cache = shr.named(mesh, cspecs)
    encdec = setup.cfg.family in ("encdec", "audio")
    place = {
        None: None,
        "params": shr.named(mesh, pspecs),
        "tokens": NamedSharding(mesh, shr.input_spec((setup.batch, 1), mesh)),
        "cache": cache,
        "state": (cache, NamedSharding(mesh, P(shr.batch_axes(mesh)))) if encdec else cache,
        "replicated": NamedSharding(mesh, P()),
    }
    return jax.jit(
        fn,
        static_argnums=static_argnums,
        in_shardings=tuple(place[a] for a in ins),
        out_shardings=tuple(place[o] for o in outs),
        donate_argnums=donate,
    )


def build_serve_fns(setup: ServeSetup, api: ModelApi | None = None, aparams: Any = None):
    """Jitted (prefill, decode) pair for a whole-batch serving step.

    ``prefill(params, batch, cache)`` fills the cache with the prompt;
    ``decode(params, token, cache, pos)`` advances one token — ``pos``
    may be a scalar (static lockstep batch) or a ``[batch]`` vector of
    per-slot positions (continuous batching).
    """
    api = api or get_model(setup.cfg)
    cfg = setup.cfg
    pctx = setup.pctx()

    def prefill_fn(params, batch, cache):
        return api.prefill(params, cfg, batch, cache, pctx=pctx)

    def decode_fn(params, token, cache, pos):
        return api.decode_step(params, cfg, token, cache, pos, pctx=pctx)

    outs = ("replicated", "state")
    return (
        _jit_step(prefill_fn, setup, api, aparams, ("params", None, "cache"), outs),
        _jit_step(decode_fn, setup, api, aparams, ("params", "tokens", "cache", None), outs),
    )


def build_slot_prefill(setup: ServeSetup, api: ModelApi | None = None, aparams: Any = None):
    """Jitted admission step: prefill ONE request into ONE cache slot.

    ``prefill_slot(params, tokens[1, S], cache, slot)`` runs the prompt
    pass on a batch-1 view of the slot's cache row and writes the filled
    row back — the other slots' cache state is untouched, so admission
    never re-prefills live requests. Returns the prompt's last-position
    logits ``[1, V]`` and the updated cache. One compilation per
    distinct prompt length (``slot`` is traced).
    """
    api = api or get_model(setup.cfg)
    cfg = setup.cfg
    pctx = setup.pctx()

    def prefill_slot(params, tokens, cache, slot):
        row = jax.tree.map(lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1), cache)
        logits, row = api.prefill(params, cfg, {"tokens": tokens}, row, pctx=pctx)
        cache = jax.tree.map(
            lambda c, r: jax.lax.dynamic_update_slice_in_dim(c, r.astype(c.dtype), slot, axis=1),
            cache,
            row,
        )
        return logits[:, -1], cache

    return _jit_step(
        prefill_slot, setup, api, aparams, ("params", None, "cache", None), ("replicated", "cache")
    )


def build_paged_prefill(setup: ServeSetup, api: ModelApi | None = None, aparams: Any = None):
    """Jitted admission step for the PAGED cache (DESIGN.md §12).

    ``prefill_slot(params, tokens[1, s], cache, pos0[1]) -> (logits[1,
    V], cache)`` runs the prompt *suffix* — the tokens past the shared
    prefix the :class:`~repro.serve.paging.PageTable` matched — as one
    causal run starting at position ``pos0``, writing K/V through the
    batch-1 ``pages`` row the engine injects for the admitted slot. No
    tree slicing: the physical pool is shared by all slots, and the page
    table alone scopes the writes, so live slots are untouched exactly
    as in :func:`build_slot_prefill`. One compilation per distinct
    suffix length.
    """
    api = api or get_model(setup.cfg)
    cfg = setup.cfg
    pctx = setup.pctx()

    def prefill_slot(params, tokens, cache, pos0):
        logits, cache = api.decode_step(params, cfg, tokens, cache, pos0, pctx=pctx)
        return logits[:, -1], cache

    return _jit_step(
        prefill_slot, setup, api, aparams, ("params", None, "cache", None), ("replicated", "cache")
    )


def build_greedy_decode(setup: ServeSetup, api: ModelApi | None = None, aparams: Any = None):
    """Jitted decode step fused with greedy token selection.

    ``decode_greedy(params, token, cache, pos) -> (next_token, cache)``
    — argmax runs inside the jit, so the engine's greedy loop never has
    to fetch a logits tensor to the host: steps chain device-resident
    and the dispatch pipeline stays full (2-3x higher tokens/sec than
    a per-step sync on small models).
    """
    api = api or get_model(setup.cfg)
    cfg = setup.cfg
    pctx = setup.pctx()

    def decode_greedy(params, token, cache, pos):
        logits, cache = api.decode_step(params, cfg, token, cache, pos, pctx=pctx)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return nxt, cache

    return _jit_step(
        decode_greedy, setup, api, aparams, ("params", "tokens", "cache", None), ("tokens", "state")
    )


def build_draft_run(setup: ServeSetup, api: ModelApi | None = None, aparams: Any = None):
    """Jitted W-step speculative draft loop (DESIGN.md §10).

    ``draft(params, token[B, 1], cache, pos[B], width) -> (run, cache)``
    chains ``width`` greedy single-token decode steps of the DRAFT tier
    inside one ``lax.scan`` — one dispatch per ROUND instead of one per
    drafted token, which is what makes drafting cheap: at serving batch
    sizes the per-dispatch overhead of a small decode graph dwarfs its
    compute, and plain per-step dispatching would cost as much as just
    decoding with the target tier. ``run[B, width]`` is the token fed
    at each step — ``[pending, d1 .. d_{width-1}]``, exactly the verify
    step's input; the LAST step's output is discarded (that step exists
    to write draft-KV at ``pos+width-1`` so a fully accepted round
    leaves no hole in the draft cache). ``width`` is static: one
    compilation per distinct round width.
    """
    api = api or get_model(setup.cfg)
    cfg = setup.cfg
    pctx = setup.pctx()

    def draft(params, token, cache, pos, width):
        def body(carry, j):
            tok, c = carry
            logits, c = api.decode_step(params, cfg, tok, c, pos + j, pctx=pctx)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            return (nxt, c), tok
        (_, cache), fed = jax.lax.scan(body, (token, cache), jnp.arange(width))
        return jnp.moveaxis(fed[:, :, 0], 0, 1), cache  # [B, width]

    return _jit_step(
        draft,
        setup,
        api,
        aparams,
        ("params", "tokens", "cache", None),
        ("tokens", "state"),
        static_argnums=(4,),
        donate_one_chip=True,
    )


def build_verify_step(setup: ServeSetup, api: ModelApi | None = None, aparams: Any = None):
    """Jitted speculative verify step (DESIGN.md §10).

    ``verify(params, tokens[B, W], cache, pos[B]) ->
    (vtok, acc, ptok, cache)`` runs ONE forward over a W-token run per
    row — row ``b``'s tokens occupy positions ``pos[b] .. pos[b]+W-1``,
    causally masked within the run — and fuses greedy selection,
    acceptance counting, and next-pending-token selection:

      * ``vtok[B, W]``: the verify tier's greedy token after each input
        position (``vtok[:, i]`` is what the target model says follows
        ``tokens[:, :i+1]``);
      * ``acc[B]``: ``1 +`` the length of the matched drafted prefix
        (``tokens[:, 1:]`` vs ``vtok[:, :-1]``), in ``1..W`` — the
        number of target-greedy tokens this round proved per row;
      * ``ptok[B, 1]``: ``vtok[b, acc[b]-1]`` — the last proven token,
        i.e. the next round's pending input. (A request whose budget
        clamps its advance below ``acc`` finishes this round, so its
        stale pending entry is never decoded.)

    Everything except the ``[B]`` ``acc`` fetch stays device-resident;
    the engine's round loop syncs exactly once per round. One
    compilation per distinct run width W (the engine clamps W near
    capacity/budget boundaries, so a trace compiles a handful).
    """
    api = api or get_model(setup.cfg)
    cfg = setup.cfg
    pctx = setup.pctx()

    def verify(params, tokens, cache, pos):
        logits, cache = api.decode_step(params, cfg, tokens, cache, pos, pctx=pctx)
        vtok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, W]
        match = (tokens[:, 1:] == vtok[:, :-1]).astype(jnp.int32)
        acc = (1 + jnp.sum(jnp.cumprod(match, axis=-1), axis=-1)).astype(jnp.int32)
        ptok = jnp.take_along_axis(vtok, acc[:, None] - 1, axis=1)
        return vtok, acc, ptok, cache

    return _jit_step(
        verify,
        setup,
        api,
        aparams,
        ("params", "tokens", "cache", None),
        ("tokens", "replicated", "tokens", "state"),
    )


# ---------------------------------------------------------------------------
# Static reference path (the pre-engine loop, kept as baseline + fallback)
# ---------------------------------------------------------------------------
def static_generate(
    setup: ServeSetup,
    params,
    batch: dict[str, Array],
    max_new_tokens: int,
    *,
    greedy: bool = True,
    key: Array | None = None,
    kv_scales: tuple[Array, Array] | None = None,
) -> Array:
    """Greedy/sampled generation for a static (lockstep) batch of prompts.

    The pre-engine serving loop: one whole-batch prefill, then
    ``max_new_tokens`` lockstep decode steps — every row pays for the
    longest request. Kept (un-deprecated) as (a) the per-request
    reference the engine's token-parity tests and the
    ``serve_continuous`` benchmark baseline compare against, (b) the
    path for families/options the slot engine does not cover
    (recurrent/enc-dec/frontend archs, legacy whole-batch sampling).

    ``kv_scales`` (calibrated ``([L, KV], [L, KV])`` —
    :func:`repro.calib.calibrate_kv_cache`) switches the cache to the
    dense static-int8 layout: the quantized reference the paged
    engine's token-identity tests compare against (same codes, no
    paging).
    """
    api = get_model(setup.cfg)
    if kv_scales is not None and not setup.kv_bits:
        setup = dataclasses.replace(setup, kv_bits=8)
    prefill_j, decode_j = build_serve_fns(setup, api, aparams=jax.eval_shape(lambda: params))
    if kv_scales is not None:
        from repro.models import transformer

        cache = transformer.init_cache(
            setup.cfg, setup.batch, setup.max_len, kv_scales=kv_scales
        )
    else:
        cache = api.init_cache(setup.cfg, setup.batch, setup.max_len)
    logits, cache = prefill_j(params, batch, cache)
    pos = batch["tokens"].shape[1] + (
        batch["frontend"].shape[1] if setup.cfg.family == "vlm" and "frontend" in batch else 0
    )
    out = []
    tok = _pick(logits, greedy, key, 0)
    out.append(tok)
    for i in range(max_new_tokens - 1):
        logits, cache = decode_j(params, tok, cache, jnp.int32(pos + i))
        tok = _pick(logits, greedy, key, i + 1)
        out.append(tok)
    return jnp.concatenate(out, axis=1)


def _pick(logits: Array, greedy: bool, key: Array | None, i: int) -> Array:
    if greedy or key is None:
        return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    k = jax.random.fold_in(key, i)
    return jax.random.categorical(k, logits[:, -1], axis=-1)[:, None].astype(jnp.int32)


def batch_generate(
    cfg: ArchConfig,
    params,
    batch: dict[str, Array],
    max_new_tokens: int,
    *,
    mesh: Mesh | None = None,
    max_len: int | None = None,
    greedy: bool = True,
    key: Array | None = None,
    flash_decode: bool = False,
    moe_impl: str | None = None,
    draft_params: Any = None,
    spec_k: int = 0,
    spec_draft: str = "model",
) -> Array:
    """Generate for a batch of same-length prompts — the one routing
    point between the engine and the static loop.

    Greedy, keyless, engine-supported, token-only calls go through
    :class:`ServeEngine` (one slot per row); sampled generation (which
    keeps the legacy whole-batch PRNG stream), frontend batches, and
    recurrent/enc-dec families take :func:`static_generate`. Both
    ``QuantizedModel.generate`` and the deprecated
    ``runtime.serve_loop.generate`` delegate here, so engine
    eligibility lives in exactly one place. ``spec_k`` (self-speculative
    decoding, DESIGN.md §10) requires engine eligibility — the static
    loop has no draft/verify path.
    """
    b, s = batch["tokens"].shape
    if max_len is None:
        max_len = s + max_new_tokens + (cfg.frontend_tokens or 0)
    engine_ok = (
        greedy
        and key is None
        and cfg.family in ENGINE_FAMILIES
        and not cfg.frontend_tokens
        and "frontend" not in batch
    )
    if (draft_params is not None or spec_k) and not engine_ok:
        raise ValueError(
            "speculative decoding runs on the slot engine, which takes greedy "
            "keyless token-only requests for transformer families — this call "
            f"(greedy={greedy}, key={'set' if key is not None else None}, "
            f"family={cfg.family!r}) falls back to the static loop, which has "
            "no draft/verify path"
        )
    if engine_ok:
        eng = ServeEngine(
            cfg,
            params,
            n_slots=b,
            max_len=max_len,
            mesh=mesh,
            flash_decode=flash_decode,
            moe_impl=moe_impl,
            draft_params=draft_params,
            spec_k=spec_k,
            spec_draft=spec_draft,
        )
        outs = eng.serve([(batch["tokens"][i], max_new_tokens) for i in range(b)])
        return jnp.asarray(np.stack(outs))
    setup = ServeSetup(
        cfg=cfg,
        mesh=mesh,
        max_len=max_len,
        batch=b,
        flash_decode=flash_decode,
        moe_impl=moe_impl or ("ep" if mesh is not None else "dense"),
    )
    return static_generate(setup, params, batch, max_new_tokens, greedy=greedy, key=key)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------
class ServeEngine:
    """Slot-based continuous-batching server for decoder LMs.

    Args:
      cfg: the architecture (``dense``/``moe`` families; recurrent,
        enc-dec and frontend archs raise — use :func:`static_generate`).
      params: float or packed parameter pytree. Packed trees are
        consumed as-is: the decode step's weight operands are uint8
        ELP_BSD codes.
      n_slots: concurrent requests = batch rows of the persistent cache.
      max_len: per-slot cache capacity (prompt + generated); a request
        reaching it is finished early and flagged ``truncated``.
      mesh: ``"auto"`` (elastic mesh over the alive devices when more
        than one is visible), an explicit ``Mesh``, or ``None``.
      flash_decode: sequence-sharded flash-decoding cache layout
        (DESIGN.md §7); composes with the paged layout (the decode step
        gathers the logical view first, then flash-attends it).
      kv_cache: ``"dense"`` (default) or ``"paged"`` — the paged pool +
        page-table layout with copy-on-write prefix sharing
        (DESIGN.md §12).
      page_size: tokens per page for ``kv_cache="paged"`` (default 16).
        Smaller pages share shorter prefixes at more table overhead.
      kv_bits: 8 to store the paged pool as int8 codes against static
        calibrated scales (0 = float, the default). Requires
        ``kv_cache="paged"`` and ``kv_scales``; inferred as 8 when
        ``kv_scales`` is passed alone.
      kv_scales: the calibrated ``(k_scale, v_scale)`` pair, each
        ``[L, KV]`` float32, from
        :func:`repro.calib.calibrate_kv_cache`.
      monitor: a :class:`StragglerMonitor` (one is created by default);
        every decode step's wall-clock is recorded.
      draft_params: optional second (aggressively low-bit, e.g. elp4)
        tier of the SAME checkpoint. With ``spec_k`` set and
        ``spec_draft="model"``, the engine decodes self-speculatively
        (DESIGN.md §10): the draft tier drafts up to ``spec_k - 1``
        tokens per round inside one scanned jit, then ``params`` — the
        high-bit/float VERIFY tier, which defines the output — checks
        the whole run in one ``spec_k``-wide forward. Output is
        token-identical to serving ``params`` non-speculatively, by
        construction.
      spec_k: speculative verify width W >= 2 (run length per round =
        W; drafted tokens verified per round = W - 1). 0 disables.
      spec_draft: the draft source. ``"model"`` decodes drafts with
        ``draft_params`` — the paper-faithful mode, fastest where the
        low-bit tier's forward is genuinely cheaper than the verify
        tier's (accelerators whose decode is weight-bandwidth-bound).
      metrics: a :class:`repro.obs.metrics.Registry`; when enabled the
        engine records TTFT/ITL histograms, queue/slot gauges,
        speculative round distributions and modeled energy (DESIGN.md
        §11). ``None`` (default) uses the shared disabled registry —
        every record is a no-op.
      trace: a :class:`repro.obs.trace.TraceLog` for per-request span
        events (JSONL). ``None`` disables tracing.
      profile: a :class:`repro.obs.trace.ProfileHook` capturing a
        ``jax.profiler`` trace around the first N decode dispatches.
        ``"ngram"`` drafts by token-recycling prompt lookup: the engine
        remembers, across its whole lifetime, which VERIFIED token
        followed each token and replays those chains — drafting costs
        no forward at all, so a round is ONE wide verify dispatch (the
        fast mode on dispatch/op-overhead-bound hosts, e.g. a CPU CI
        runner, where any sequential draft loop costs as much per step
        as the target tier). Either way acceptance only modulates
        SPEED; the verify tier makes the output stream identical.
    """

    def __init__(
        self,
        cfg: ArchConfig,
        params: Any,
        *,
        n_slots: int = 4,
        max_len: int = 512,
        mesh: Mesh | str | None = "auto",
        target_model: int = 16,
        flash_decode: bool = False,
        kv_cache: str = "dense",
        page_size: int = 16,
        kv_bits: int = 0,
        kv_scales: Any = None,
        moe_impl: str | None = None,
        monitor: StragglerMonitor | None = None,
        draft_params: Any = None,
        spec_k: int = 0,
        spec_draft: str = "model",
        metrics: Registry | None = None,
        trace: TraceLog | None = None,
        profile: ProfileHook | None = None,
    ):
        if cfg.family not in ENGINE_FAMILIES:
            raise ValueError(
                f"ServeEngine drives the transformer cache contract "
                f"(families {ENGINE_FAMILIES}); {cfg.family!r} archs serve through "
                "repro.serve.static_generate"
            )
        if cfg.frontend_tokens:
            raise ValueError(
                "ServeEngine requests are token-only; frontend (vlm/audio) prompts "
                "serve through repro.serve.static_generate"
            )
        if kv_cache not in ("dense", "paged"):
            raise ValueError(f'kv_cache must be "dense" or "paged", got {kv_cache!r}')
        self._paged = kv_cache == "paged"
        if kv_scales is not None and not kv_bits:
            kv_bits = 8
        if kv_bits and not self._paged:
            raise ValueError(
                "quantized KV cache requires kv_cache='paged' — the dense engine "
                "cache keeps the float layout (the dense static-int8 reference "
                "runs through repro.serve.static_generate(kv_scales=...))"
            )
        if kv_bits and kv_bits != 8:
            raise ValueError(
                f"kv_bits={kv_bits}: the cache stores int8 codes, so serving "
                "bit-width is 8 (calibrate scales for other widths with "
                "repro.calib.calibrate_kv_cache(bits=...) for analysis only)"
            )
        if kv_bits and kv_scales is None:
            raise ValueError(
                "kv_bits without kv_scales: static cache quantization needs "
                "calibrated per-(layer, head) scales — run "
                "repro.calib.calibrate_kv_cache(params, cfg, token_batches) and "
                "pass the (k_scale, v_scale) pair"
            )
        self.spec_k = int(spec_k)
        self.spec_draft = str(spec_draft)
        if self.spec_draft not in ("model", "ngram"):
            raise ValueError(
                f'spec_draft must be "model" or "ngram", got {self.spec_draft!r}'
            )
        if self.spec_k == 0:
            if draft_params is not None:
                raise ValueError(
                    "draft_params without spec_k: speculative serving takes the "
                    "draft tier AND the verify width (spec_k >= 2), or neither"
                )
        elif self.spec_k < 2:
            raise ValueError(
                f"spec_k is the verify width: need >= 2 (got {self.spec_k}) — width 1 "
                "verifies nothing and is strictly slower than plain decode"
            )
        elif self.spec_draft == "model" and draft_params is None:
            raise ValueError(
                'spec_draft="model" drafts with a second weight tier — pass '
                'draft_params, or draft from the token history with '
                'spec_draft="ngram"'
            )
        elif self.spec_draft == "ngram" and draft_params is not None:
            raise ValueError(
                'spec_draft="ngram" drafts from the engine\'s verified token '
                "history, not a weight tier — drop draft_params or use "
                'spec_draft="model"'
            )
        if mesh == "auto":
            from repro.runtime.elastic import make_mesh

            mesh = make_mesh(target_model=target_model) if len(jax.devices()) > 1 else None
        self.cfg = cfg
        self.mesh = mesh
        self.setup = ServeSetup(
            cfg=cfg,
            mesh=mesh,
            max_len=max_len,
            batch=n_slots,
            moe_impl=moe_impl or ("ep" if mesh is not None else "dense"),
            flash_decode=flash_decode,
            page_size=int(page_size) if self._paged else 0,
            kv_bits=int(kv_bits),
        )
        self._api = get_model(cfg)
        if mesh is not None:
            params = _place(params, mesh)
        aparams = jax.eval_shape(lambda: params)
        self.params = params
        if self._paged:
            self._prefill = build_paged_prefill(self.setup, self._api, aparams=aparams)
        else:
            self._prefill = build_slot_prefill(self.setup, self._api, aparams=aparams)
        _, self._decode = build_serve_fns(self.setup, self._api, aparams=aparams)
        self._decode_greedy = build_greedy_decode(self.setup, self._api, aparams=aparams)
        if self._paged:
            from repro.models import transformer
            from repro.serve.paging import PageTable

            scales = None
            if kv_bits:
                scales = (
                    jnp.asarray(kv_scales[0], jnp.float32),
                    jnp.asarray(kv_scales[1], jnp.float32),
                )
            cache = self._new_cache(
                lambda: transformer.init_paged_cache(
                    cfg, n_slots, max_len, page_size=self.setup.page_size, kv_scales=scales
                )
            )
            self._pager = PageTable(
                n_slots, max_len, self.setup.page_size, n_pages=cache["k"].shape[1]
            )
        else:
            cache = self._new_cache(lambda: self._api.init_cache(cfg, n_slots, max_len))
            self._pager = None
        self._cache = cache
        # speculative state: the verify step always runs on the target
        # params. A "model" drafter additionally gets its own jitted
        # prefill/draft-run pair and its OWN cache (same geometry, same
        # sharding rules — both tiers coexist on the mesh); an "ngram"
        # drafter gets a vocab-sized transition table (which verified
        # token last followed each token, engine-wide) plus the host
        # copy of each slot's pending token the lookup chains from.
        self.draft_params = draft_params
        if self.spec_k:
            self._verify = build_verify_step(self.setup, self._api, aparams=aparams)
            self._spec_width = self.spec_k
            if self.spec_draft == "model":
                if mesh is not None:
                    self.draft_params = _place(draft_params, mesh)
                adraft = jax.eval_shape(lambda: self.draft_params)
                if self._paged:
                    # the draft tier gets its OWN physical pool but maps
                    # it through the SAME page table: logical positions
                    # coincide, so shared-prefix admissions skip the
                    # draft prefill of those pages too
                    self._draft_prefill = build_paged_prefill(
                        self.setup, self._api, aparams=adraft
                    )
                    dcache = self._new_cache(
                        lambda: transformer.init_paged_cache(
                            cfg,
                            n_slots,
                            max_len,
                            page_size=self.setup.page_size,
                            kv_scales=scales,
                        )
                    )
                else:
                    self._draft_prefill = build_slot_prefill(
                        self.setup, self._api, aparams=adraft
                    )
                    dcache = self._new_cache(lambda: self._api.init_cache(cfg, n_slots, max_len))
                self._draft_run = build_draft_run(self.setup, self._api, aparams=adraft)
                self._draft_cache = dcache
            else:
                self._ngram = np.full(cfg.vocab, -1, np.int32)
                self._pending = np.zeros(n_slots, np.int32)
        self.monitor = monitor or StragglerMonitor()
        # observability (DESIGN.md §11): instrument handles are resolved
        # once here; with a disabled registry they are shared null
        # objects whose record/inc/set is a single `pass`, so the hot
        # loop's cost is one attribute lookup per event, metrics on or
        # off. Table II energy per emitted token is modeled once at
        # startup from the tree that serves (fmt of the packed leaves,
        # bytes actually streamed per decode step).
        self.metrics = metrics or NULL_REGISTRY
        self.trace = trace
        self.profile = profile
        m = self.metrics
        self._m_ttft = m.histogram("serve.ttft_s")
        self._m_itl = m.histogram("serve.itl_s")
        self._m_prefill = m.histogram("serve.prefill_s")
        self._m_request = m.histogram("serve.request_s")
        self._m_queue = m.gauge("serve.queue_depth")
        self._m_live = m.gauge("serve.slots_live")
        self._m_tokens = m.counter("serve.tokens_total")
        self._m_finished = m.counter("serve.requests_finished_total")
        self._m_energy = m.counter("serve.energy_nj_total")
        # paged-cache occupancy (DESIGN.md §12): refreshed each step
        self._m_pages_used = m.gauge("serve.cache.pages_used")
        self._m_pages_shared = m.gauge("serve.cache.pages_shared")
        self._m_prefix_hits = m.counter("serve.cache.prefix_hits_total")
        self.energy = lm_token_energy(cfg, params)
        self._draft_energy = (
            lm_token_energy(cfg, self.draft_params)
            if self.spec_k and self.spec_draft == "model"
            else None
        )
        if self.spec_k:
            self._m_width = m.histogram(
                "serve.spec.round_width", lo=1.0, growth=2.0**0.25, n_buckets=24
            )
            self._m_acc = m.histogram(
                "serve.spec.accepted_per_round", lo=1.0, growth=2.0**0.25, n_buckets=24
            )
        self._sched = SlotScheduler(n_slots)
        self._requests: dict[int, Request] = {}
        self._next_rid = 0
        # per-slot state: next cache write position (host — the
        # scheduler needs it synchronously) and the last generated token
        # (device-resident [n_slots, 1]: the greedy loop chains it from
        # step to step without ever fetching it)
        self._pos = np.zeros(n_slots, np.int32)
        self._tok_dev = jnp.zeros((n_slots, 1), jnp.int32)
        if mesh is not None:  # where the decode step leaves it: one admission program
            tok_spec = shr.input_spec((n_slots, 1), mesh)
            self._tok_dev = jax.device_put(self._tok_dev, NamedSharding(mesh, tok_spec))
        self.steps = 0
        self._decode_steps = 0
        self._prefills = 0
        self._tokens_generated = 0
        self._completed = 0
        self._truncated = 0
        self._spec_rounds = 0
        self._tokens_drafted = 0
        self._tokens_accepted = 0

    # -- request lifecycle ---------------------------------------------------
    def submit(self, tokens, max_new_tokens: int, *, key=None) -> int:
        """Queue one request; returns its id (results via :meth:`result`).

        Admission happens inside :meth:`step` when a slot frees up. On
        the dense cache that is one prompt-length prefill into the
        slot's row; on the paged cache the allocator first matches the
        prompt's full pages against the shared-prefix index
        (acquiring refcounts — ``stats()["cache"]["prefix_hits"]``
        counts the pages skipped this way), allocates private pages for
        the rest, and prefills only the unmatched suffix. Either way
        the request's first emitted token comes from that admission
        dispatch, so TTFT is one prefill regardless of sharing.
        """
        prompt = np.array(tokens, np.int32).reshape(-1)
        # frozen for its lifetime: admission hands `prompt` to jnp.asarray
        # (potentially zero-copy), which is only alias-safe because no one
        # can write the buffer afterwards
        prompt.setflags(write=False)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if prompt.size + max_new_tokens > self.setup.max_len:
            raise ValueError(
                f"request needs {prompt.size} prompt + {max_new_tokens} new tokens "
                f"= {prompt.size + int(max_new_tokens)} cache positions, but the "
                f"engine's per-slot capacity is max_len={self.setup.max_len} — "
                "raise max_len or lower max_new_tokens (decoding past capacity "
                "would wrap into neighbouring positions)"
            )
        if key is not None and self.spec_k:
            raise ValueError(
                "speculative serving is greedy-only (acceptance compares argmax "
                "streams); submit sampled requests to a non-speculative engine"
            )
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt, max_new_tokens=int(max_new_tokens), key=key)
        req.t_submit = time.perf_counter()
        self._requests[rid] = req
        self._sched.submit(req)
        if self.trace is not None:
            self.trace.event(
                "submit", rid, prompt_len=int(prompt.size), max_new=int(max_new_tokens)
            )
        return rid

    def evict(self, rid: int) -> np.ndarray:
        """Cancel a live/queued request, freeing its slot immediately.

        Returns the tokens generated so far. The slot needs no cleanup:
        the next occupant's prefill overwrites ``[0, S)`` and the
        mask-past-pos contract hides everything beyond its own writes.
        """
        req = self._requests[rid]
        if req.done:
            return req.tokens()
        if req.slot is not None:
            slot = req.slot
            self._sched.finish(slot)
            self._pos[slot] = 0
            if self._paged:
                self._pager.release(slot)
        else:
            self._sched.cancel(req)
        req.truncated = True
        self._truncated += 1
        return req.tokens()

    def result(self, rid: int) -> np.ndarray:
        return self._requests[rid].tokens()

    def release(self, rid: int) -> np.ndarray:
        """Fetch a request's tokens AND retire its bookkeeping.

        :meth:`serve` releases every request it created, so a
        long-running engine does not accumulate one ``Request`` per
        served prompt; ``submit``/``result`` users call this (or keep
        using ``result`` and accept the growth)."""
        return self._requests.pop(rid).tokens()

    # -- stepping ------------------------------------------------------------
    def step(self) -> bool:
        """Admit queued requests into free slots, then run one decode step
        for every live slot. Returns whether any work happened.

        Greedy-only steps stay device-resident: selection runs inside
        the jitted step, requests log lazy ``(token_vector, slot)``
        entries, and nothing blocks on the device — the dispatch
        pipeline stays full. A step with any sampled (keyed) request
        falls back to fetching logits.
        """
        with span("serve.step"):
            progressed = False
            for slot, req in self._sched.ready():
                with span("serve.admit", rid=req.rid, slot=slot, prompt_len=int(req.prompt.size)):
                    self._admit(slot, req)
                progressed = True

            self._m_queue.set(self._sched.queued)
            self._m_live.set(len(self._sched.live))
            if self._paged:
                self._m_pages_used.set(self._pager.pages_used)
                self._m_pages_shared.set(self._pager.pages_shared)
            live = self._sched.live
            if live:
                with span("serve.decode", live=len(live)):
                    if self.spec_k:
                        self._spec_round(live)
                    else:
                        self._decode_live(live)
                progressed = True
            self.steps += 1
            return progressed

    def _admit(self, slot: int, req: Request) -> None:
        """Admit one queued request into ``slot``: prefill its prompt
        (the unmatched suffix on a paged cache), emit its first token."""
        req.t_admit = time.perf_counter()
        if self._paged:
            # page-table admission (DESIGN.md §12): acquire the
            # matched shared-prefix pages, allocate the rest, and
            # prefill only the unmatched SUFFIX as one causal run
            # starting past the shared tokens. The batch-1 pages row
            # scopes the writes; no other slot's pages appear in it.
            n_shared = self._pager.admit(slot, req.prompt)
            self._m_prefix_hits.inc(n_shared // self.setup.page_size)
            # to_device COPIES (the blessed crossing): the
            # allocator mutates `table` in place on the next
            # admit/release while the async dispatch may not have
            # read this view yet
            row = self._pager.to_device(slot)
            pos0 = jnp.asarray([n_shared], jnp.int32)
            # repro: noqa[R001] prompt is frozen read-only at submit
            suffix = jnp.asarray(req.prompt[None, n_shared:])
            logits, newc = self._prefill(
                self.params, suffix, {**self._cache, "pages": row}, pos0
            )
            self._cache = {**newc, "pages": self._pager.to_device()}
            self._pager.register(slot, req.prompt)
        else:
            logits, self._cache = self._prefill(
                self.params,
                # repro: noqa[R001] prompt is frozen read-only at submit
                jnp.asarray(req.prompt[None]),
                self._cache,
                jnp.int32(slot),
            )
        self._prefills += 1
        if self.spec_k and self.spec_draft == "model":
            # the draft tier keeps its own cache in lockstep: same
            # prompt, same slot. Its prefill logits are discarded —
            # every EMITTED token, including the prefill token below,
            # comes from the verify tier, which is what makes the
            # output token-identical to non-speculative serving.
            if self._paged:
                _, newdc = self._draft_prefill(
                    self.draft_params,
                    suffix,
                    {**self._draft_cache, "pages": row},
                    pos0,
                )
                self._draft_cache = {**newdc, "pages": self._pager.to_device()}
            else:
                _, self._draft_cache = self._draft_prefill(
                    self.draft_params,
                    # repro: noqa[R001] prompt is frozen read-only at submit
                    jnp.asarray(req.prompt[None]),
                    self._draft_cache,
                    jnp.int32(slot),
                )
        if req.key is None and self.spec_k and self.spec_draft == "ngram":
            # the lookup drafter chains from the pending token's
            # VALUE, so admission syncs it (one scalar fetch riding
            # the prefill dispatch it already paid for)
            # repro: noqa[R004] deliberate: ngram drafting needs the token value
            first = int(np.asarray(jnp.argmax(logits, axis=-1))[0])
            req.out.append(first)
            self._pending[slot] = first
        elif req.key is None:
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [1], device
            req.out.append((first, 0))
            self._tok_dev = self._tok_dev.at[slot, 0].set(first[0])
        else:
            # repro: noqa[R004] deliberate: sampling draws on host (§9)
            tok = self._select(req, np.asarray(logits)[0])
            req.out.append(tok)
            self._tok_dev = self._tok_dev.at[slot, 0].set(tok)
        self._tokens_generated += 1
        self._pos[slot] = req.prompt.size
        # the admission prefill emits the request's FIRST token, so
        # this dispatch-clocked timestamp is its TTFT sample (the
        # same sync-point clocking the straggler monitor uses)
        req.t_first = time.perf_counter()
        self._m_ttft.record(req.t_first - req.t_submit)
        self._m_prefill.record(req.t_first - req.t_admit)
        self._m_tokens.inc()
        self._m_energy.inc(self.energy["total_nj"])
        if self.trace is not None:
            self.trace.event(
                "admit",
                req.rid,
                slot=slot,
                prompt_len=int(req.prompt.size),
                prefill_s=req.t_first - req.t_admit,
                ttft_s=req.t_first - req.t_submit,
            )
        self._maybe_finish(slot, req)

    def _decode_live(self, live: dict[int, Request]) -> None:
        """One decode step for every live slot (no speculation)."""
        # hand the dispatch its OWN copy of the position vector:
        # jnp.asarray can zero-copy-alias a host numpy buffer on
        # CPU, and self._pos is mutated in place below while the
        # (async) decode may not have read it yet
        pos = jnp.asarray(np.array(self._pos))
        n_live = len(live)  # snapshot: _maybe_finish pops from live
        cache_in = self._dispatch_cache()
        t0 = time.perf_counter()
        if all(r.key is None for r in live.values()):
            nxt, self._cache = self._decode_greedy(
                self.params, self._tok_dev, cache_in, pos
            )
            self._tok_dev = nxt
            # dispatch-clocked: once the device queue back-pressures,
            # dispatch wall-clock tracks true step time
            dt = time.perf_counter() - t0
            self.monitor.record(dt)
            for slot, req in list(live.items()):
                req.out.append((nxt, slot))
                self._tokens_generated += 1
                self._m_itl.record(dt)
                self._pos[slot] += 1
                self._maybe_finish(slot, req)
        else:
            logits, self._cache = self._decode(
                self.params, self._tok_dev, cache_in, pos
            )
            # repro: noqa[R004] deliberate: sampled decode fetches logits (§9)
            logits = np.asarray(jax.block_until_ready(logits))
            dt = time.perf_counter() - t0
            self.monitor.record(dt)
            toks = np.zeros(self._sched.n_slots, np.int32)
            for slot, req in list(live.items()):
                tok = self._select(req, logits[slot, -1])
                req.out.append(tok)
                toks[slot] = tok
                self._tokens_generated += 1
                self._m_itl.record(dt)
                self._pos[slot] += 1
                self._maybe_finish(slot, req)
            self._tok_dev = jnp.asarray(toks[:, None])
        self._m_tokens.inc(n_live)
        self._m_energy.inc(self.energy["total_nj"] * n_live)
        if self.profile is not None:
            self.profile.step()
        if self.trace is not None:
            self.trace.event("decode", None, live=n_live, dt_s=dt)
        self._decode_steps += 1

    def run(self) -> None:
        """Drive :meth:`step` until queue and slots are empty."""
        while self._sched.busy:
            self.step()

    def serve(
        self, requests: Sequence[tuple], *, arrivals: Sequence[int] | None = None
    ) -> list[np.ndarray]:
        """Serve ``[(prompt_tokens, max_new_tokens), ...]`` to completion.

        ``arrivals`` (optional, non-decreasing) holds per-request arrival
        times in engine steps relative to this call — requests are
        submitted once that many steps have run (the mixed-length
        staggered-trace shape the benchmark drives); an idle engine
        fast-forwards to the next arrival. Returns generated tokens in
        request order.
        """
        reqs = list(requests)
        if arrivals is None:
            rids = [self.submit(t, n) for t, n in reqs]
            self.run()
            if self.profile is not None:
                self.profile.stop()
            return [self.release(r) for r in rids]
        arrivals = list(arrivals)
        if len(arrivals) != len(reqs):
            raise ValueError(
                f"arrivals has {len(arrivals)} entries for {len(reqs)} requests"
            )
        if arrivals != sorted(arrivals):
            raise ValueError("arrivals must be non-decreasing (FIFO trace)")
        rids: list[int | None] = [None] * len(reqs)
        start = self.steps
        i = 0
        while i < len(reqs) or self._sched.busy:
            while i < len(reqs) and (
                self.steps - start >= arrivals[i] or not self._sched.busy
            ):
                rids[i] = self.submit(*reqs[i])
                i += 1
            self.step()
        if self.profile is not None:
            self.profile.stop()
        return [self.release(r) for r in rids]

    # -- introspection -------------------------------------------------------
    def cache_stats(self) -> dict:
        """KV-cache layout, occupancy and byte costs (DESIGN.md §12).

        Always returns the same key set, so dashboards diff layouts:

        * ``layout`` / ``kv_bits`` / ``page_size`` — the configured
          geometry (``"dense"`` reports ``page_size=0``);
        * ``pages_total`` / ``pages_used`` / ``pages_shared`` /
          ``prefix_hits`` — page-pool occupancy and the running count of
          shared-prefix pages acquired by admissions (all 0 for dense);
        * ``bytes_per_token`` — modeled DRAM bytes of cache read per
          decoded token at full context (codes + static scales for the
          quantized layout, measured from the cache arrays' dtypes);
        * ``slot_bytes`` — cache bytes one slot *holds*: the dense slot
          stripe, or (paged) the measured average of privately
          allocated pages per admission times the page byte size — the
          number the ``max_slots_at_fixed_mem`` benchmark entry divides
          by, and where prefix sharing shows up as savings.
        """
        cfg, setup = self.cfg, self.setup
        n_layers = cfg.n_dec_layers or cfg.n_layers
        elem = self._cache["k"].dtype.itemsize
        token_bytes = 2 * n_layers * cfg.n_kv_heads * cfg.hd * elem
        scale_bytes = 2 * n_layers * cfg.n_kv_heads * 4 if setup.kv_bits else 0
        if self._paged:
            pg = self._pager
            page_bytes = token_bytes * setup.page_size
            private = pg.pages_allocated / pg.admissions if pg.admissions else pg.pmax
            return {
                "layout": "paged",
                "kv_bits": setup.kv_bits,
                "page_size": setup.page_size,
                "pages_total": pg.pages_total,
                "pages_used": pg.pages_used,
                "pages_shared": pg.pages_shared,
                "prefix_hits": pg.prefix_hits,
                "bytes_per_token": token_bytes * setup.max_len + scale_bytes,
                "slot_bytes": private * page_bytes,
            }
        return {
            "layout": "dense",
            "kv_bits": 0,
            "page_size": 0,
            "pages_total": 0,
            "pages_used": 0,
            "pages_shared": 0,
            "prefix_hits": 0,
            "bytes_per_token": token_bytes * setup.max_len,
            "slot_bytes": token_bytes * setup.max_len,
        }

    def stats(self) -> dict:
        """Serving counters + the straggler monitor's slow-step report.

        Always includes a ``"cache"`` sub-dict (:meth:`cache_stats`:
        layout, page occupancy, prefix-hit counts, modeled cache bytes
        per token). Under speculative serving the dict gains a
        ``"speculative"`` sub-dict (drafted/accepted counts and the
        aggregate acceptance rate), and the same acceptance fields are
        folded into the ``"straggler"`` report — a slow round and a
        rejected round look identical in wall-clock, so the two
        diagnostics read together.
        """
        st = {
            "cache": self.cache_stats(),
            "steps": self.steps,
            "decode_steps": self._decode_steps,
            "prefills": self._prefills,
            "tokens_generated": self._tokens_generated,
            "requests_completed": self._completed,
            "requests_truncated": self._truncated,
            "live_slots": len(self._sched.live),
            "n_slots": self._sched.n_slots,
            "mesh": dict(self.mesh.shape) if self.mesh is not None else None,
            "energy_nj_per_token": self.energy["total_nj"],
            "kernel_dispatch": self.kernel_dispatch(),
            "straggler": self.monitor.report(),
        }
        if self.metrics.enabled:
            st["latency"] = {
                "ttft_p50_s": self._m_ttft.percentile(50),
                "ttft_p99_s": self._m_ttft.percentile(99),
                "itl_p50_s": self._m_itl.percentile(50),
                "itl_p99_s": self._m_itl.percentile(99),
                "request_p50_s": self._m_request.percentile(50),
                "request_p99_s": self._m_request.percentile(99),
            }
        if self.spec_k:
            rate = (
                self._tokens_accepted / self._tokens_drafted
                if self._tokens_drafted
                else 1.0
            )
            spec = {
                "spec_k": self.spec_k,
                "drafter": self.spec_draft,
                "rounds": self._spec_rounds,
                "tokens_drafted": self._tokens_drafted,
                "tokens_accepted": self._tokens_accepted,
                "acceptance_rate": rate,
            }
            st["speculative"] = spec
            st["straggler"] = {
                **st["straggler"],
                "tokens_drafted": self._tokens_drafted,
                "tokens_accepted": self._tokens_accepted,
                "acceptance_rate": rate,
            }
        return st

    def kernel_dispatch(self) -> dict:
        """Which matmul impl each packed decode GEMM resolves to.

        Walks the packed weight tree and resolves every distinct
        ``[K, N]`` decode-step GEMM (``M`` = the slot batch) exactly the
        way ``layers.matmul(impl="auto")`` does at trace time
        (:func:`repro.kernels.ops.select_impl`): the shape rule's kernel
        and tiles on TPU, XLA elsewhere and for stacked (expert)
        weights. Layer weights are judged per layer
        slice, as the layer scan hands them to the matmul. On a mesh of
        several devices a 2-D weight runs per shard
        (:func:`repro.kernels.ops.sharded_matmul`) and is resolved at the
        shape one device computes. Observability for "is the fused decode
        kernel actually on?" — keyed ``MxKxN|fmt|layout``, each value
        recording the impl, how it was chosen (``heuristic`` /
        ``structural``, or ``sharded`` per shard), its
        ``(block_m, block_n, block_k)`` tiles (None on the XLA path), the
        dtype the dot's operands take for the model's activations
        (:func:`repro.kernels.elp_bsd_matmul.mxu_operand_dtype`; float32
        on the XLA path), and how many weights share the shape. A sharded
        shape also records its ``split`` (``col``: N over the mesh,
        ``row``: K over the mesh and a sum after, ``whole``: the weight on
        every device) and its ``local`` ``MxKxN``.
        """
        from repro.kernels.elp_bsd_matmul import mxu_operand_dtype
        from repro.kernels.ops import PackedWeight, select_impl, shard_shape

        m = self._sched.n_slots
        sharded = self.mesh is not None and self.mesh.size > 1
        out: dict[str, dict] = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            self.params, is_leaf=lambda l: isinstance(l, PackedWeight)
        ):
            if not isinstance(leaf, PackedWeight):
                continue
            k, n = leaf.shape
            key = f"{m}x{k}x{n}|{leaf.fmt_name}|{'nib' if leaf.nibble else 'u8'}"
            if key in out:
                out[key]["count"] += 1
                continue
            scanned = getattr(path[0], "key", None) == "blocks"
            stacked = leaf.codes.ndim - scanned != 2
            local = (m, k, n) if stacked or not sharded else shard_shape(m, leaf, self.mesh)
            impl, blocks, source = select_impl(*local, nibble=leaf.nibble, stacked=stacked)
            kernel = impl != "xla"
            operand = mxu_operand_dtype(leaf.fmt, self.cfg.dtype) if kernel else jnp.float32
            out[key] = {
                "impl": impl,
                "source": source,
                "blocks": blocks if kernel else None,
                "operand": jnp.dtype(operand).name,
                "count": 1,
            }
            if sharded and not stacked:
                k_axis, n_axis = leaf.split or (None, None)
                out[key].update(
                    source="sharded",
                    split="row" if k_axis else "col" if n_axis else "whole",
                    local="x".join(map(str, local)),
                )
        return out

    def compiled_decode(self):
        """The compiled greedy decode step — the graph the continuous
        loop actually runs (``.as_text()`` shows which kernels it calls)."""
        return self._decode_greedy.lower(
            self.params,
            jnp.zeros((self._sched.n_slots, 1), jnp.int32),
            jax.eval_shape(lambda: self._cache),
            jnp.asarray(np.array(self._pos)),
        ).compile()

    def decode_cost(self) -> dict:
        """HLO cost (FLOPs / bytes / collectives) of the compiled greedy
        decode step — the evidence that packed serving moves code bytes,
        not a dequantized weight tree."""
        from repro.launch.hlo_stats import compiled_cost

        return compiled_cost(self.compiled_decode())

    # -- internals -----------------------------------------------------------
    def _new_cache(self, make) -> Any:
        """``make()``'s cache tree, built straight into its shardings on the
        engine's mesh: no device ever holds the whole cache."""
        if self.mesh is None:
            return make()
        cspecs = shr.cache_specs_tree(
            jax.eval_shape(make), self.mesh, prefer_seq=self.setup.flash_decode
        )
        return jax.jit(make, out_shardings=shr.named(self.mesh, cspecs))()

    def _dispatch_cache(self, cache: Any = None) -> Any:
        """The cache tree a dispatch consumes.

        For the paged layout the ``pages`` leaf is refreshed through
        :meth:`PageTable.to_device` — the blessed copying crossing (the
        allocator is host-authoritative: admission and release mutate
        ``self._pager.table`` in place between dispatches, and a
        zero-copy ``jnp.asarray`` alias would let the next admission
        rewrite the page mapping under a still-pending dispatch); the
        dense layout passes the persistent cache straight through.
        """
        cache = self._cache if cache is None else cache
        if not self._paged:
            return cache
        return {**cache, "pages": self._pager.to_device()}

    def _spec_round(self, live: dict[int, Request]) -> None:
        """One speculative draft/verify round (DESIGN.md §10).

        Round width ``W`` is the adaptive target (below) clamped so no
        live slot's writes run past its cache capacity, and shrunk to
        the largest remaining budget (no point drafting 7 when every
        live request wants <= 2 more tokens). A "model" round is
        exactly TWO dispatches — the scanned W-step draft loop
        (:func:`build_draft_run`) producing the run ``[pending, d1 ..
        d_{W-1}]``, then the W-wide verify forward on the target tier
        fusing greedy selection, acceptance counting and pending-token
        choice. An "ngram" round builds the run on the host (a walk of
        the engine's verified-transition table from each slot's pending
        token) and is ONE dispatch, the verify forward. Either way the
        loop syncs the ``[B]`` ``acc`` vector once per round (the ngram
        round also pulls the small ``[B, W]`` verified-token matrix: it
        both feeds the table and lets outputs resolve without touching
        the device again).

        Width adapts AIMD-style: a fully-accepted round widens the next
        target by one (up to ``spec_k``), a round accepting under half
        its width drops the target to just past what was accepted. Cold
        ngram tables and chaotic draft tiers therefore cost about a
        plain wide-2 decode per round instead of a full-width miss, and
        recovery back to ``spec_k`` takes a handful of good rounds —
        width never changes WHAT is emitted, only how much is risked
        per round, so output identity is untouched.

        Rollback is free: a slot that accepted ``take < W`` tokens just
        advances ``pos`` by ``take`` — the rejected suffix positions
        hold garbage in the cache(s), but the mask-past-pos contract
        plus write-before-attend ordering means the next round
        overwrites them before anything reads them (the same argument
        that makes slot reuse safe). Free slots ride along at ``pos=0``
        with their writes masked the same way.
        """
        pos_np = np.array(self._pos)
        width = max(
            1,
            min(
                self._spec_width,
                min(int(self.setup.max_len - pos_np[s]) for s in live),
                max(r.remaining for r in live.values()),
            ),
        )
        pos = jnp.asarray(pos_np)
        t0 = time.perf_counter()
        if self.spec_draft == "model":
            run, self._draft_cache = self._draft_run(
                self.draft_params,
                self._tok_dev,
                self._dispatch_cache(self._draft_cache),
                pos,
                width,
            )
        else:
            run = jnp.asarray(self._ngram_run(live, width))
        vtok, acc, ptok, self._cache = self._verify(
            self.params, run, self._dispatch_cache(), pos
        )
        if self.spec_draft == "model":
            self._tok_dev = ptok
        # dispatch-clocked like the plain path: one record per round
        dt = time.perf_counter() - t0
        self.monitor.record(dt)
        # repro: noqa[R004] deliberate: the round's one blocking sync (§10)
        acc_np = np.asarray(acc)
        # repro: noqa[R004] deliberate: ngram rounds pull the [B, W] run once (§10)
        vtok_np = np.asarray(vtok) if self.spec_draft == "ngram" else None
        acc_sum = 0
        n_live = len(live)  # snapshot: _maybe_finish pops from live
        for slot, req in list(live.items()):
            a = int(acc_np[slot])
            acc_sum += a
            if vtok_np is None:
                take = req.advance(vtok, slot, width, a)
            else:
                take = min(a, req.remaining)
                req.out.extend(int(t) for t in vtok_np[slot, :take])
                # every transition inside the accepted run is a VERIFIED
                # greedy step of the target tier — teach the table all of
                # them (pending -> v0 -> ... -> v_{a-1})
                chain = np.concatenate(
                    ([self._pending[slot]], vtok_np[slot, :a])
                ).astype(np.int64)
                self._ngram[chain[:-1]] = chain[1:]
                self._pending[slot] = int(vtok_np[slot, a - 1])
            req.drafted += width - 1
            req.accepted += a - 1
            self._tokens_drafted += width - 1
            self._tokens_accepted += a - 1
            self._tokens_generated += take
            self._m_acc.record(a)
            # the round emitted `take` tokens over one dispatch: each is
            # one inter-token-latency sample of dt / take (speculation's
            # whole point is that this is below the plain-decode ITL)
            if take:
                itl = dt / take
                for _ in range(take):
                    self._m_itl.record(itl)
                self._m_tokens.inc(take)
                self._m_energy.inc(self.energy["total_nj"] * take)
            self._pos[slot] += take
            self._maybe_finish(slot, req)
        self._m_width.record(width)
        if self._draft_energy is not None:
            # a model-draft round additionally streams the draft tier's
            # weights once per drafted position
            self._m_energy.inc(self._draft_energy["total_nj"] * width)
        if self.profile is not None:
            self.profile.step()
        if self.trace is not None:
            self.trace.event(
                "round", None, live=n_live, width=width, accepted=acc_sum - n_live, dt_s=dt
            )
        mean_a = acc_sum / n_live
        if mean_a >= width:
            self._spec_width = min(self.spec_k, max(self._spec_width, width + 1))
        elif mean_a < width / 2:
            self._spec_width = max(2, int(mean_a) + 1)
        self._spec_rounds += 1
        # W draft steps + 1 verify forward, or just the verify forward
        self._decode_steps += (width + 1) if self.spec_draft == "model" else 1

    def _ngram_run(self, live: dict[int, Request], width: int) -> np.ndarray:
        """Token-recycling draft run: walk the verified-transition table.

        Row ``slot`` is ``[pending, t1 .. t_{width-1}]`` where each
        ``t_j`` is what last followed ``t_{j-1}`` in ANY verified stream
        this engine produced (prompt-lookup generalized across requests
        and engine lifetime). An unseen token repeats — a draft that is
        almost surely rejected, which the width controller then prices
        in. Rows of free slots stay zero; their cache writes are masked
        like any other past-pos garbage.
        """
        run = np.zeros((self._sched.n_slots, width), np.int32)
        for slot in live:
            t = int(self._pending[slot])
            run[slot, 0] = t
            for j in range(1, width):
                nxt = int(self._ngram[t])
                if nxt >= 0:
                    t = nxt
                run[slot, j] = t
        return run

    def _select(self, req: Request, logits_row: np.ndarray) -> int:
        if req.key is None:
            return int(np.argmax(logits_row))
        k = jax.random.fold_in(req.key, len(req.out))
        return int(jax.random.categorical(k, jnp.asarray(logits_row)))

    def _maybe_finish(self, slot: int, req: Request) -> None:
        full = self._pos[slot] >= self.setup.max_len
        if req.remaining <= 0 or full:
            if full and req.remaining > 0:
                req.truncated = True
                self._truncated += 1
            self._sched.finish(slot)
            self._completed += 1
            self._pos[slot] = 0
            if self._paged:
                self._pager.release(slot)
            req.t_finish = time.perf_counter()
            self._m_request.record(req.t_finish - req.t_submit)
            self._m_finished.inc()
            if self.trace is not None:
                self.trace.event(
                    "finish",
                    req.rid,
                    slot=slot,
                    tokens=len(req.out),
                    truncated=req.truncated,
                    total_s=req.t_finish - req.t_submit,
                )
