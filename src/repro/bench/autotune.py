"""Impl-aware multi-backend autotuner for the quantized kernel layer.

The seed kernels ran hardcoded 128-cubed blocks — and a hardcoded
*implementation* — for every shape, which is how the conv0-class cliff
happened (Pallas-by-heuristic 10x slower than the XLA fallback on
shapes nobody measured). This module makes both choices measured: per
``(M, K, N, fmt, code_layout, backend)`` it times every implementation
in :data:`IMPLS` — the tiled Pallas kernel across its block-size
candidates, the fused decode-step kernel, and the XLA
dequantize-then-matmul fallback — and persists one entry per impl in a
JSON cache. The dispatch layer (``quantized_matmul(impl="auto")``,
``quantized_conv2d``, ``flash_decode_attention``, and therefore the
serve decode jit) resolves each shape to its measured winner with a
trace-time dict lookup.

Numeric-stability contract: by default the search pins ``block_k`` to
the kernel default. Splitting K differently regroups the float32
accumulation (``acc += dot(x_tile, w_tile)`` per K step), which changes
last-ulp rounding — and the repo's tests pin packed outputs bit-exactly
against the default tiling. ``block_m``/``block_n`` only re-tile which
output elements share a kernel invocation; every output element still
sums the same products in the same order, so those candidates are
bit-identical and safe to tune freely. Pass ``bit_stable=False`` to
search K splits too (e.g. on real TPU where the extra headroom is worth
re-baselining the tolerances).

Cache layout (``autotune_cache.json``, committed next to this module)::

    {"schema_version": 2,
     "entries": {"cpu|pallas|elp_bsd_a4|nib|128x256x128":
                   {"blocks": [128, 128, 128], "wall_us": 812.4,
                    "candidates": 4, "bit_stable": true},
                 "cpu|xla|elp_bsd_a4|nib|128x256x128":
                   {"blocks": [128, 128, 128], "wall_us": 201.3, ...},
                 ...}}

Key axes, in order: backend, impl, format, code layout (``nib``/``u8``),
shape. The backend leads because interpret-mode wall-clock on CPU and
Mosaic wall-clock on TPU rank candidates differently; a cache produced
on one never leaks onto the other. Schema v1 keys (no impl segment)
are migrated on read as ``impl="pallas"`` — that is what v1 timings
measured (DESIGN.md §14). ``REPRO_AUTOTUNE_CACHE`` overrides the cache
path (tests point it at a tmpdir).

Flash-decode block sizes share the cache under the ``flash_decode``
impl segment: ``cpu|flash_decode|attn|s<S>|BxHxHD`` entries carry the
seq-chunk size as ``blocks[1]`` (see :func:`lookup_flash_block_s`).
"""
from __future__ import annotations

import json
import os
from typing import Iterable, Sequence

import jax
import numpy as np
from jax.sharding import AxisType

DEFAULT_BLOCKS = (128, 128, 128)
# Implementations the tuner races per shape. "pallas" is the tiled
# decode+matmul kernel, "pallas_fused" the decode-step kernel (single-
# pass shift-add form on non-TPU backends), "xla" the dequantize-then-
# matmul fallback.
IMPLS = ("pallas", "pallas_fused", "xla")
CACHE_SCHEMA_VERSION = 2
CACHE_ENV = "REPRO_AUTOTUNE_CACHE"

# In-memory cache of the parsed file, keyed by path so tests that
# repoint CACHE_ENV never see stale entries.
_loaded: dict[str, dict] = {}


def cache_path() -> str:
    return os.environ.get(
        CACHE_ENV, os.path.join(os.path.dirname(__file__), "autotune_cache.json")
    )


def cache_key(
    m: int,
    k: int,
    n: int,
    fmt_name: str,
    nibble: bool,
    backend: str,
    impl: str = "pallas",
) -> str:
    return f"{backend}|{impl}|{fmt_name}|{'nib' if nibble else 'u8'}|{m}x{k}x{n}"


def flash_cache_key(b: int, h: int, hd: int, s: int, backend: str) -> str:
    """Key for a flash-decode seq-chunk entry (``blocks[1]`` = chunk)."""
    return f"{backend}|flash_decode|attn|s{s}|{b}x{h}x{hd}"


def _valid_entry(ent) -> bool:
    if not isinstance(ent, dict):
        return False
    blocks = ent.get("blocks")
    return (
        isinstance(blocks, list)
        and len(blocks) == 3
        and all(isinstance(b, int) and b > 0 for b in blocks)
    )


def _migrate_v1(key: str, ent: dict) -> tuple[str, dict] | None:
    """v1 ``backend|fmt|mode|MxKxN`` → v2 with ``impl="pallas"`` spliced in.

    v1 entries were produced by timing ``impl="pallas"`` block candidates
    only, so that is the key family they land in — but their ``wall_us``
    is dropped: it was never raced against the other impls, and letting
    it vote in :func:`lookup_impl` would elect interpret-mode Pallas on
    CPU unopposed. Migrated entries keep steering block sizes; impl
    selection waits for a v2 retune.
    """
    parts = key.split("|")
    if len(parts) != 4:
        return None
    backend, fmt, mode, shape = parts
    return f"{backend}|pallas|{fmt}|{mode}|{shape}", {
        k: v for k, v in ent.items() if k != "wall_us"
    }


def _read_cache(path: str) -> dict:
    """Parsed ``entries`` dict; corrupt or missing files read as empty.

    Corruption falls back rather than raising because the cache is an
    optimization: a bad file must degrade to default blocks, not take
    down a serve path that asked for ``"auto"``. Schema v1 files are
    migrated in memory (keys gain the ``pallas`` impl segment).
    """
    if path in _loaded:
        return _loaded[path]
    entries: dict = {}
    try:
        with open(path) as f:
            doc = json.load(f)
        version = doc.get("schema_version") if isinstance(doc, dict) else None
        if version in (1, CACHE_SCHEMA_VERSION):
            raw = doc.get("entries", {})
            if isinstance(raw, dict):
                for key, ent in raw.items():
                    if not _valid_entry(ent):
                        continue
                    if version == 1:
                        migrated = _migrate_v1(key, ent)
                        if migrated is None:
                            continue
                        key, ent = migrated
                    entries[key] = ent
    except (OSError, json.JSONDecodeError):
        entries = {}
    _loaded[path] = entries
    return entries


def invalidate_memory_cache() -> None:
    """Drop the in-process cache (tests; after an external refresh)."""
    _loaded.clear()


def cache_entries() -> dict[str, dict]:
    """Read-only snapshot of the parsed autotune cache.

    Keys are :func:`cache_key` strings (``backend|impl|fmt|mode|MxKxN``).
    Used by ``repro.api`` to record which tuned tilings apply to a
    quantized artifact's weight shapes.
    """
    return dict(_read_cache(cache_path()))


def lookup_blocks(
    m: int,
    k: int,
    n: int,
    *,
    fmt_name: str,
    nibble: bool,
    backend: str | None = None,
    impl: str = "pallas",
) -> tuple[int, int, int]:
    """Resolve ``(block_m, block_n, block_k)`` for a matmul shape + impl.

    Exact-key cache hit wins; a miss returns :data:`DEFAULT_BLOCKS`
    (never raises — "auto" must be safe to request for shapes nobody
    tuned yet).
    """
    backend = backend or jax.default_backend()
    entries = _read_cache(cache_path())
    ent = entries.get(cache_key(m, k, n, fmt_name, nibble, backend, impl=impl))
    if ent is None:
        return DEFAULT_BLOCKS
    bm, bn, bk = ent["blocks"]
    if nibble and bk % 2:
        return DEFAULT_BLOCKS
    return (bm, bn, bk)


def lookup_impl(
    m: int,
    k: int,
    n: int,
    *,
    fmt_name: str,
    nibble: bool,
    backend: str | None = None,
) -> tuple[str | None, tuple[int, int, int]]:
    """Measured-best ``(impl, blocks)`` for a shape, or ``(None, defaults)``.

    Scans every impl's cache entry for the shape and returns the one with
    the smallest recorded ``wall_us``. ``None`` means nobody tuned this
    shape on this backend — the caller falls back to its heuristic.
    """
    backend = backend or jax.default_backend()
    entries = _read_cache(cache_path())
    best: tuple[str, float, list] | None = None
    for impl in IMPLS:
        ent = entries.get(cache_key(m, k, n, fmt_name, nibble, backend, impl=impl))
        if ent is None:
            continue
        wall = ent.get("wall_us")
        if not isinstance(wall, (int, float)):
            continue
        if best is None or wall < best[1]:
            best = (impl, float(wall), ent["blocks"])
    if best is None:
        return None, DEFAULT_BLOCKS
    bm, bn, bk = best[2]
    if nibble and bk % 2:
        return best[0], DEFAULT_BLOCKS
    return best[0], (bm, bn, bk)


def lookup_flash_block_s(
    b: int, h: int, hd: int, s: int, *, backend: str | None = None
) -> int | None:
    """Tuned flash-decode seq-chunk size, or ``None`` (= one-shot slice).

    ``None`` on a miss keeps the untuned path byte-identical to the
    pre-autotune behavior; a tuned chunk must divide the shard-local
    sequence length to apply.
    """
    backend = backend or jax.default_backend()
    entries = _read_cache(cache_path())
    ent = entries.get(flash_cache_key(b, h, hd, s, backend))
    if ent is None:
        return None
    block_s = ent["blocks"][1]
    if block_s <= 0 or s % block_s or block_s >= s:
        return None
    return block_s


def candidate_blocks(
    m: int,
    k: int,
    n: int,
    *,
    nibble: bool,
    bit_stable: bool = True,
    sizes: Sequence[int] = (128, 256, 512),
) -> list[tuple[int, int, int]]:
    """MXU-aligned candidate tilings for one shape.

    Prunes blocks larger than the next 128-multiple of the dim (pure
    padding waste) and, in ``bit_stable`` mode, fixes ``block_k`` at the
    default so every candidate is bit-identical (see module docstring).
    """

    def dims(size: int) -> list[int]:
        ceil128 = -(-max(size, 1) // 128) * 128
        out = [s for s in sizes if s <= ceil128]
        return out or [sizes[0]]

    kdims = [DEFAULT_BLOCKS[2]] if bit_stable else [s for s in dims(k) if not nibble or s % 2 == 0]
    cands = []
    for bm in dims(m):
        for bn in dims(n):
            for bk in kdims:
                cands.append((bm, bn, bk))
    return cands


def _impl_candidates(
    impl: str, m: int, k: int, n: int, *, nibble: bool, bit_stable: bool, backend: str
) -> list[tuple[int, int, int]]:
    """Block candidates to race for one impl (empty = impl not applicable)."""
    if impl == "pallas":
        return candidate_blocks(m, k, n, nibble=nibble, bit_stable=bit_stable)
    if impl == "pallas_fused":
        from repro.kernels.fused_decode import MAX_FUSED_M

        if m > MAX_FUSED_M:
            return []
        if backend == "tpu":
            # block_m is fixed (M rides whole); search n/k tiles only.
            return sorted(
                {(DEFAULT_BLOCKS[0], bn, bk)
                 for _, bn, bk in candidate_blocks(m, k, n, nibble=nibble, bit_stable=bit_stable)}
            )
        # Off-TPU the fused impl lowers to the single-pass XLA form,
        # which has no block parameters — one candidate.
        return [DEFAULT_BLOCKS]
    if impl == "xla":
        return [DEFAULT_BLOCKS]
    raise ValueError(f"unknown impl {impl!r}; expected one of {IMPLS}")


def autotune_matmul(
    m: int,
    k: int,
    n: int,
    fmt,
    *,
    nibble: bool | None = None,
    bit_stable: bool = True,
    iters: int = 3,
    warmup: int = 1,
    seed: int = 0,
    backend: str | None = None,
    write: bool = True,
) -> dict:
    """Race every impl (and its block candidates) for one shape.

    Builds a seeded random activation/weight pair, times
    ``quantized_matmul`` under every ``(impl, blocks)`` candidate, and
    (optionally) merges the best entry *per impl* into the persistent
    cache — ``lookup_impl`` then picks the cross-impl winner at dispatch
    time. Returns the winner's entry plus the full cross-impl ranking
    (``{"key", "impl", "blocks", "wall_us", "candidates", "ranking"}``).

    On CPU the Pallas kernels run in interpret mode, so their *absolute*
    numbers are not TPU-representative — but that is exactly what makes
    the per-backend keying load-bearing: the CPU cache steers dispatch
    away from interpret-mode kernels, the TPU cache (produced by this
    same call on a TPU host) ranks the real Mosaic lowerings.
    """
    import jax.numpy as jnp

    from repro.core.elp_bsd import PRESET_FORMATS
    from repro.kernels.ops import pack_weight, quantized_matmul

    if isinstance(fmt, str):
        fmt = PRESET_FORMATS[fmt]
    actual = jax.default_backend()
    if backend is not None and backend != actual:
        # The measurement always runs on the local backend; accepting a
        # foreign label would store interpreter-ranked winners under the
        # other backend's keys and poison its cache.
        raise ValueError(
            f"cannot tune for backend {backend!r} on a {actual!r} host; "
            "run the tuner on the target backend"
        )
    backend = actual
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.02, jnp.float32)
    pw, _ = pack_weight(w, fmt, compensate=False, nibble=nibble)

    from repro.bench.harness import time_fn

    ranking = []
    for impl in IMPLS:
        for blocks in _impl_candidates(
            impl, m, k, n, nibble=pw.nibble, bit_stable=bit_stable, backend=backend
        ):
            t = time_fn(
                lambda i=impl, b=blocks: quantized_matmul(x, pw, impl=i, block_sizes=b),
                iters=iters,
                warmup=warmup,
            )
            ranking.append({"impl": impl, "blocks": list(blocks), "wall_us": t.min_us})
    ranking.sort(key=lambda r: r["wall_us"])

    new_entries = {}
    for impl in IMPLS:
        impl_ranked = [r for r in ranking if r["impl"] == impl]
        if not impl_ranked:
            continue
        best = impl_ranked[0]
        new_entries[cache_key(m, k, n, fmt.name, pw.nibble, backend, impl=impl)] = {
            "blocks": best["blocks"],
            "wall_us": best["wall_us"],
            "candidates": len(impl_ranked),
            "bit_stable": bool(bit_stable),
        }
    if write:
        write_entries(new_entries)
    winner = ranking[0]
    key = cache_key(m, k, n, fmt.name, pw.nibble, backend, impl=winner["impl"])
    return {
        "key": key,
        "impl": winner["impl"],
        "blocks": winner["blocks"],
        "wall_us": winner["wall_us"],
        "candidates": len(ranking),
        "bit_stable": bool(bit_stable),
        "ranking": ranking,
    }


def autotune_flash_decode(
    b: int,
    s: int,
    h: int,
    hd: int,
    *,
    kv: int | None = None,
    iters: int = 3,
    warmup: int = 1,
    seed: int = 0,
    backend: str | None = None,
    chunks: Sequence[int] = (128, 256, 512),
    write: bool = True,
) -> dict:
    """Race seq-chunk sizes for the flash-decode attention shape.

    Candidate ``block_s = 0`` is the one-shot slice (the untuned
    behavior); proper divisors of ``s`` from ``chunks`` stream the
    shard-local KV slice through the softmax-stats combine. The winner
    lands under :func:`flash_cache_key` with the chunk in ``blocks[1]``
    (0 = one-shot).
    """
    import jax.numpy as jnp

    from repro.models.context import ParallelCtx
    from repro.models.flash_decode import flash_decode_attention

    actual = jax.default_backend()
    if backend is not None and backend != actual:
        raise ValueError(
            f"cannot tune for backend {backend!r} on a {actual!r} host; "
            "run the tuner on the target backend"
        )
    backend = actual
    kv = kv or h
    mesh = jax.make_mesh(
        (1, jax.device_count()), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
    pctx = ParallelCtx(mesh=mesh, batch_axes=("data",), model_axis="model", flash_decode=True)
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(b, s, kv, hd)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(b, s, kv, hd)), jnp.float32)
    pos = jnp.int32(s - 1)

    from repro.bench.harness import time_fn

    s_loc = s // mesh.shape["model"]
    cands = [0] + [c for c in chunks if 0 < c < s_loc and s_loc % c == 0]
    ranking = []
    with mesh:
        for block_s in cands:
            # repro: noqa[R003] one jit per tuned candidate; traces once, warmup eats compile
            fn = jax.jit(
                lambda q_, k_, v_, p_, bs=block_s: flash_decode_attention(
                    q_, k_, v_, p_, pctx=pctx, block_s=bs or None
                )
            )
            t = time_fn(lambda f=fn: f(q, ck, cv, pos), iters=iters, warmup=warmup)
            ranking.append({"block_s": block_s, "wall_us": t.min_us})
    ranking.sort(key=lambda r: r["wall_us"])
    best = ranking[0]
    key = flash_cache_key(b, h, hd, s, backend)
    entry = {
        "blocks": [1, int(best["block_s"]), 1],
        "wall_us": best["wall_us"],
        "candidates": len(ranking),
        "bit_stable": best["block_s"] == 0,
    }
    if write:
        write_entries({key: entry})
    return {"key": key, "ranking": ranking, **entry}


def sweep_nibble(m: int, k: int, n: int, fmt, **kw) -> list[dict]:
    """Autotune a 4-bit shape under both storage modes (u8 and nibble).

    Each mode lands under its own cache key family; the returned results
    let callers compare decode cost vs HBM savings per backend.
    """
    return [autotune_matmul(m, k, n, fmt, nibble=nib, **kw) for nib in (False, True)]


def write_entries(new_entries: dict) -> None:
    """Merge entries into the cache file (read-modify-write, atomic rename).

    Unlike the read path (which degrades a corrupt file to "no cache"),
    writing REFUSES to proceed over an existing file it cannot parse:
    merging into the empty fallback would silently wipe every entry the
    file held (e.g. committed TPU tunings after a merge-conflict
    marker, or a future schema version). Delete or fix the file first.
    A readable schema-v1 file is migrated and rewritten as v2.
    """
    path = cache_path()
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
            ok = isinstance(doc, dict) and doc.get("schema_version") in (1, CACHE_SCHEMA_VERSION)
        except (OSError, json.JSONDecodeError):
            ok = False
        if not ok:
            raise RuntimeError(
                f"refusing to overwrite unreadable/foreign autotune cache {path}; "
                "delete it (or fix the JSON / schema_version) and re-run"
            )
    entries = dict(_read_cache(path))
    entries.update(new_entries)
    doc = {"schema_version": CACHE_SCHEMA_VERSION, "entries": dict(sorted(entries.items()))}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    _loaded[path] = entries


def autotune_shapes(shapes: Iterable[tuple], **kw) -> list[dict]:
    """Tune a batch of ``(m, k, n, fmt, nibble)`` specs (bench.sh entry)."""
    out = []
    for m, k, n, fmt, nib in shapes:
        out.append(autotune_matmul(m, k, n, fmt, nibble=nib, **kw))
    return out
