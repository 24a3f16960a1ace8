"""Jitted public ops over packed ELP_BSD weights.

``PackedWeight`` is the runtime artifact of conversion: a code buffer
(uint8, optionally nibble-packed), per-cell scale factors, and the
static format. It is a registered pytree so it flows through jit / pjit
/ scan like any weight.

All conversion goes through the unified engine
(:func:`repro.core.convert.convert_tensor`); this module only adds the
storage layout (nibble packing, logical-shape bookkeeping) and the
execution paths:

``quantized_matmul`` picks between:
  * ``impl="pallas"`` — the fused decode+matmul kernel (TPU target,
    interpret-mode on CPU),
  * ``impl="xla"``    — dequantize-then-dot in plain jnp (XLA writes the
    decoded weight to HBM before the dot reads it), used for stacked
    (expert) weights, off TPU, and as the reference datapath.

A 2-D weight on a mesh of several devices runs one of these per device
under ``jax.shard_map``, on the device's own shard of the codes
(:func:`quantized_matmul`, DESIGN.md §5).

Convolution weights pack through :func:`pack_conv_weight` (the 4-D
``[H, W, Cin, Cout]`` tensor flattens to ``[H*W*Cin, Cout]`` im2col
layout; ``source_shape`` remembers the conv layout for the XLA path) and
execute via :func:`repro.kernels.conv.quantized_conv2d`.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.convert import convert_tensor, nibble_pack
from repro.core.elp_bsd import ElpBsdFormat, PRESET_FORMATS
from repro.kernels import ref as kref
from repro.kernels.elp_bsd_matmul import elp_bsd_matmul
from repro.kernels.fused_decode import MAX_FUSED_M, fused_decode_matmul

Array = jax.Array
F32 = jnp.float32


@dataclasses.dataclass
class PackedWeight:
    """ELP_BSD-encoded weight matrix ``[..., K, N]``.

    Attributes:
      codes: uint8 code buffer; ``[..., K, N]`` (u8 mode) or
        ``[..., ceil(K/2), N]`` (nibble mode, 4-bit formats only).
        Leading dims are stack dims (scan layers / experts); ``lax.scan``
        and indexing slice them off naturally because PackedWeight is a
        registered pytree whose aux data describes only the logical
        trailing (K, N).
      sf: scale factors, float32, keepdims-broadcastable against the
        decoded ``[..., K, N]`` codes — ``[..., 1, 1]`` for per-tensor /
        per-slice conversion, ``[..., 1, N]`` for per-output-channel.
      fmt_name: key into :data:`repro.core.elp_bsd.PRESET_FORMATS`.
      nibble: whether codes are nibble-packed along K.
      shape: logical (K, N) of the trailing weight dims.
      source_shape: original nd layout for non-matmul weights (set to
        ``(kh, kw, cin, cout)`` by :func:`pack_conv_weight`; None for
        plain matmuls).
      act_scale / act_bits: optional *static* activation quantizer for
        this weight's input (calibrated serve path, DESIGN.md §6): when
        set, ``quantized_matmul`` fake-quantizes ``x`` against the
        compile-time constant ``act_scale`` — no runtime ``max|x|``
        reduction in the decode graph. Set by
        ``repro.api_schemes.pack_lm_params`` from a
        :class:`~repro.calib.policy.CalibrationTable`.
      mesh / split: where the weight was placed
        (:func:`repro.runtime.sharding.mark_splits`): the mesh, and the
        mesh axes the codes' trailing ``(K-row, N)`` dims are split
        over — ``(None, "model")`` column-parallel, ``("model", None)``
        row-parallel, ``(None, None)`` whole on each device. A traced
        array under an ``Auto`` mesh carries no spec (and no mesh, when
        it was lowered from bare shapes), so the placement rides here,
        as static data, to the matmul that runs the weight per shard.
    """

    codes: Array
    sf: Array
    fmt_name: str
    nibble: bool
    shape: tuple[int, int]
    source_shape: tuple[int, ...] | None = None
    act_scale: float | None = None
    act_bits: int | None = None
    split: tuple[str | None, str | None] | None = None
    mesh: Any = None

    @property
    def fmt(self) -> ElpBsdFormat:
        return PRESET_FORMATS[self.fmt_name]

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.codes.shape))

    def tree_flatten_with_keys(self):
        ga = jax.tree_util.GetAttrKey
        return ((ga("codes"), self.codes), (ga("sf"), self.sf)), (
            self.fmt_name,
            self.nibble,
            self.shape,
            self.source_shape,
            self.act_scale,
            self.act_bits,
            self.split,
            self.mesh,
        )

    def tree_flatten(self):
        return (self.codes, self.sf), (
            self.fmt_name,
            self.nibble,
            self.shape,
            self.source_shape,
            self.act_scale,
            self.act_bits,
            self.split,
            self.mesh,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        codes, sf = children
        return cls(codes, sf, *aux)


jax.tree_util.register_pytree_with_keys_class(PackedWeight)


# jitted whole: run op by op, a conversion compiles each of its
# primitives anew for every weight shape
@functools.partial(
    jax.jit, static_argnames=("fmt", "compensate", "group_axes", "granularity", "nibble")
)
def pack_weight(
    w: Array,
    fmt: ElpBsdFormat | str,
    *,
    compensate: bool = True,
    group_axes: tuple[int, ...] | None = None,
    granularity: str = "per_tensor",
    nibble: bool | None = None,
) -> tuple[PackedWeight, Array]:
    """Convert a ``[..., K, N]`` weight into (packed codes, dequantized values).

    Thin wrapper over the conversion engine: runs Sec. V quantization
    (+ Algorithm 1 when ``compensate``, grouped over the contracting dim
    by default) at the requested scale ``granularity``, then encodes
    level indices to raw bit codes (nibble-packed along K for 4-bit
    formats; odd K pads one code row, sliced off on decode). Returns the
    dequantized values too so callers can decide between holding floats
    (training) or codes (serving).
    """
    if isinstance(fmt, str):
        fmt = PRESET_FORMATS[fmt]
    if w.ndim < 2:
        raise ValueError(
            f"pack_weight operates on [..., K, N] matmul weights, got shape {w.shape}"
        )
    if nibble is None:
        nibble = fmt.bits_per_weight <= 4
    if group_axes is None:
        # Matmul contract: trailing dims are [K, N]; Algorithm 1 groups the
        # contracting rows of each output column. (The engine's rank-based
        # default would read a 4-D stack [L, E, K, N] as a conv layout.)
        group_axes = (w.ndim - 2,)
    ct = convert_tensor(
        w, fmt, granularity=granularity, compensate=compensate, group_axes=group_axes
    )
    codes = ct.codes()
    if nibble:
        codes = nibble_pack(codes, axis=-2)
    pw = PackedWeight(
        codes=codes,
        sf=ct.sf,
        fmt_name=fmt.name,
        nibble=bool(nibble),
        shape=(int(w.shape[-2]), int(w.shape[-1])),
    )
    return pw, ct.values.astype(w.dtype)


@functools.partial(jax.jit, static_argnames=("fmt", "compensate", "granularity", "nibble"))
def pack_conv_weight(
    w: Array,
    fmt: ElpBsdFormat | str,
    *,
    compensate: bool = True,
    granularity: str = "per_tensor",
    nibble: bool | None = None,
) -> tuple[PackedWeight, Array]:
    """Convert a conv ``[kh, kw, cin, cout]`` weight to im2col-packed codes.

    Quantization and Algorithm 1 run on the conv layout (groups = the
    spatial dims, the paper's intra-channel case); the emitted codes are
    laid out ``[K=kh*kw*cin, N=cout]`` so the packed matmul kernel
    consumes them directly on extracted patches. ``granularity`` may be
    per-tensor or per-channel (per-slice has no meaning for one conv).
    Returns the packed weight and the dequantized values in conv layout.
    """
    if isinstance(fmt, str):
        fmt = PRESET_FORMATS[fmt]
    if w.ndim != 4:
        raise ValueError(
            "pack_conv_weight operates on [kh, kw, cin, cout] weights, "
            f"got shape {w.shape}"
        )
    if granularity == "per_slice":
        raise ValueError("per_slice granularity is for stacked matmuls, not convs")
    if nibble is None:
        nibble = fmt.bits_per_weight <= 4
    ct = convert_tensor(
        w, fmt, granularity=granularity, compensate=compensate, group_axes=(0, 1)
    )
    kh, kw, cin, cout = w.shape
    codes = ct.codes().reshape(kh * kw * cin, cout)
    if nibble:
        codes = nibble_pack(codes, axis=-2)
    pw = PackedWeight(
        codes=codes,
        # sf varies along cout at most, so the [K, N] view is [1, -1].
        sf=ct.sf.reshape(1, -1),
        fmt_name=fmt.name,
        nibble=bool(nibble),
        shape=(kh * kw * cin, cout),
        source_shape=(kh, kw, cin, cout),
    )
    return pw, ct.values.astype(w.dtype)


def packed_tree_bytes(tree, *, packed_only: bool = False) -> int:
    """Weight-storage bytes of a (possibly partially) packed pytree.

    The single packed-size accounting walk (``models/cnn`` and
    ``runtime/quantized_params`` delegate here): a :class:`PackedWeight`
    leaf costs its code buffer plus float32 scale factors; any other
    leaf costs ``size * itemsize`` unless ``packed_only`` drops it from
    the tally. Works on real arrays and on ``ShapeDtypeStruct`` trees
    (the allocation-free dry-run path) alike.
    """
    total = 0
    for leaf in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, PackedWeight)):
        if isinstance(leaf, PackedWeight):
            total += leaf.nbytes + int(np.prod(leaf.sf.shape)) * 4
        elif not packed_only:
            total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


# Code-to-weight decode outside a kernel runs under this scope in the
# device trace; the Pallas kernels decode inside ``elp_bsd_matmul``.
DECODE_SCOPE = "elp_bsd_decode"


def dequantize(pw: PackedWeight) -> Array:
    """Decode a PackedWeight back to float32 ``[..., K, N]`` (XLA path)."""
    with jax.named_scope(DECODE_SCOPE):
        codes = kref.unpack_nibbles_k(pw.codes) if pw.nibble else pw.codes
        w = kref.decode_values(codes, pw.fmt) * pw.sf
        return w[..., : pw.shape[0], : pw.shape[1]]


def dequantize_shift_add(pw: PackedWeight) -> Array:
    """Decode via the shift-add decomposition — bit-identical to
    :func:`dequantize`, fewer vector ops (single-pass XLA form of the
    fused kernel datapath, DESIGN.md §14)."""
    with jax.named_scope(DECODE_SCOPE):
        codes = kref.unpack_nibbles_k(pw.codes) if pw.nibble else pw.codes
        w = kref.decode_values_shift_add(codes, pw.fmt) * pw.sf
        return w[..., : pw.shape[0], : pw.shape[1]]


def dequantize_nd(pw: PackedWeight) -> Array:
    """Decode to the source layout (conv ``[kh, kw, cin, cout]``, etc.)."""
    w = dequantize(pw)
    if pw.source_shape is None:
        return w
    with jax.named_scope(DECODE_SCOPE):
        return w.reshape(pw.source_shape)


def dequantize_tree(tree):
    """Decode every PackedWeight leaf back to float32 (source layouts).

    The float twin of a packed tree: numerically exactly what the
    packed execution paths compute from the stored codes, in a pytree
    any float forward / eval_fn accepts. Non-packed leaves pass
    through untouched.
    """
    return jax.tree_util.tree_map(
        lambda l: dequantize_nd(l) if isinstance(l, PackedWeight) else l,
        tree,
        is_leaf=lambda l: isinstance(l, PackedWeight),
    )


def weight_mesh(x: Array):
    """The mesh ``x`` lives on, read at trace time, or None for one device.

    An array committed to a mesh names it in its own sharding, and so
    does its tracer; a context mesh (``jax.sharding.use_mesh``) names it
    otherwise. Axes that a ``shard_map`` has made manual hold one shard
    per device, so a value inside one lives on one device. Anything else
    lives on one device, however many the host has.
    """
    mesh = jax.typeof(x).sharding.mesh
    if mesh.empty:
        mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    manual = jax.sharding.AxisType.Manual
    auto = [n for n, t in zip(mesh.axis_names, mesh.axis_types) if t != manual]
    return mesh if int(np.prod([mesh.shape[n] for n in auto])) > 1 else None


# The TPU tile rule (DESIGN.md §14). A grid step costs about 0.35 µs on
# a v5e whatever it holds, so each step carries about _TPU_PANEL_BYTES of
# codes; the activation block stays under _TPU_X_BLOCK_BYTES counted at
# 4 bytes an element, whatever x's dtype.
_TPU_PANEL_BYTES = 1 << 20
_TPU_X_BLOCK_BYTES = 2 << 20
_TPU_MAX_BLOCK_N = 512
_TPU_MAX_BLOCK_M = 512


def _tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 up to ``cap`` that divides ``dim``
    (rounded up to 128, which the ops wrapper pads to)."""
    dim = -(-dim // 128) * 128
    return max(t for t in range(128, cap + 1, 128) if dim % t == 0)


def tpu_blocks(m: int, k: int, n: int, *, nibble: bool) -> tuple[str, tuple[int, int, int]]:
    """``(impl, (block_m, block_n, block_k))`` for a packed matmul on TPU.

    Decode-sized M (≤ ``MAX_FUSED_M``) takes the fused kernel, whose whole
    M strip stays in VMEM unpadded; a larger M takes the tiled kernel with
    M blocks of up to 512 rows. ``block_n`` and ``block_k`` are the
    largest 128 multiples dividing N and K within the panel and
    activation budgets, so the 128-aligned shapes of real layers pad
    nothing.
    """
    impl = "pallas_fused" if m <= MAX_FUSED_M else "pallas"
    block_m = m if impl == "pallas_fused" else min(-(-m // 16) * 16, _TPU_MAX_BLOCK_M)
    block_n = _tile(n, _TPU_MAX_BLOCK_N)
    row_bytes = block_n // 2 if nibble else block_n  # code bytes per K row
    cap = min(_TPU_PANEL_BYTES // row_bytes, _TPU_X_BLOCK_BYTES // (4 * block_m))
    return impl, (block_m, block_n, _tile(k, cap))


def select_impl(
    m: int, k: int, n: int, *, nibble: bool, stacked: bool
) -> tuple[str, tuple[int, int, int] | None, str]:
    """Resolve ``impl="auto"`` for one packed matmul: ``(impl, blocks, source)``.

    ``m, k, n`` are the shape one device computes: a weight on a mesh
    runs per shard (:func:`quantized_matmul`), so its local shape. Stacked
    weights take the XLA path (``source="structural"``): the Pallas
    kernels take one ``[K, N]``. Otherwise the shape decides
    (``"heuristic"``): on TPU the kernel and tiles :func:`tpu_blocks`
    derives, elsewhere XLA with no blocks.
    """
    if stacked:
        return "xla", None, "structural"
    if jax.default_backend() == "tpu":
        return (*tpu_blocks(m, k, n, nibble=nibble), "heuristic")
    return "xla", None, "heuristic"


_FORCED_IMPL: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "forced_impl", default=None
)


@contextlib.contextmanager
def force_impl(impl: str):
    """Trace every ``impl="auto"`` packed matmul in the block as ``impl``.

    Reference runs use ``force_impl("xla")`` to pin the plain
    dequantize-then-dot datapath that the kernels are checked against.
    It acts when a function is traced, so jit a fresh function inside it.
    """
    token = _FORCED_IMPL.set(impl)
    try:
        yield
    finally:
        _FORCED_IMPL.reset(token)


def _pad_to(x: Array, axis: int, mult: int) -> Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def quantized_matmul(x: Array, pw: PackedWeight, *, impl: str = "pallas", **kw) -> Array:
    """``x[..., K] @ dequant(pw)[K, N]`` with fused in-VMEM decode.

    See :func:`_quantized_matmul` for the arguments. ``impl="auto"``
    becomes the impl of an enclosing :func:`force_impl` here, before the
    jit, so that a forced and an automatic trace never share a cache
    entry. A 2-D weight on a mesh of several devices runs per shard
    (:func:`sharded_matmul`).
    """
    if impl == "auto":
        impl = _FORCED_IMPL.get() or impl
    mesh = None
    if pw.codes.ndim == 2:
        mesh = weight_mesh(pw.codes) if pw.mesh is None else pw.mesh
    if mesh is not None and mesh.size > 1:
        return sharded_matmul(x, pw, mesh, impl=impl, **kw)
    with jax.named_scope("elp_bsd_matmul"):
        return _quantized_matmul(x, pw, impl=impl, **kw)


# The partial sums of a row-parallel GEMM are added across the mesh under
# this scope in the device trace.
COLLECTIVE_SCOPE = "tp_collective"


def _rows_spec(mesh, m: int, used: tuple) -> tuple[str, ...] | None:
    """Mesh axes the ``m`` activation rows split over: every axis the
    weight does not, when their devices divide ``m``."""
    rest = tuple(a for a in mesh.axis_names if a not in used)
    if not rest or m % int(np.prod([mesh.shape[a] for a in rest])):
        return None
    return rest


def shard_shape(m: int, pw: PackedWeight, mesh) -> tuple[int, int, int]:
    """``(M, K, N)`` that one device computes in :func:`sharded_matmul`
    of ``x[m, K]`` against ``pw`` (a per-layer slice, if stacked)."""
    k_axis, n_axis = pw.split or (None, None)
    k, n = pw.shape
    if k_axis is not None:
        rows = pw.codes.shape[-2] // mesh.shape[k_axis]
        k = 2 * rows if pw.nibble else rows
    if n_axis is not None:
        n //= mesh.shape[n_axis]
    rows = _rows_spec(mesh, m, (k_axis, n_axis)) or ()
    return m // int(np.prod([mesh.shape[a] for a in rows])), k, n


def sharded_matmul(x: Array, pw: PackedWeight, mesh, *, impl: str, out_dtype=None, **kw) -> Array:
    """``quantized_matmul`` of a 2-D weight on a mesh, one shard per device.

    Each device runs :func:`_quantized_matmul` (the kernel, at tiles of
    its local shape) on its own codes under ``jax.shard_map``. The
    weight's :attr:`PackedWeight.split` says how the codes are laid out:

    * column-parallel (N over an axis): each device computes its output
      columns, and the output stays split over that axis;
    * row-parallel (K over an axis): each device contracts its K rows
      (whole nibble pairs: code row ``r`` holds rows ``2r`` and ``2r+1``)
      with its slice of ``x`` into a float32 partial sum, and a ``psum``
      over the axis adds them;
    * neither: the whole weight on every device.

    Activation rows split over the mesh's other axes where they divide.
    Scales follow the weight: per-channel ``sf`` splits with N, and is
    whole on each device otherwise (``runtime/sharding._scale_spec``).
    """
    k_axis, n_axis = pw.split or (None, None)
    k, n = pw.shape
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if k_axis is not None and pw.nibble:
        x2 = _pad_to(x2, 1, 2 * pw.codes.shape[0])  # an odd K's pad row, zero
    rows = _rows_spec(mesh, x2.shape[0], (k_axis, n_axis))
    split_sf = n_axis is not None and pw.sf.ndim and pw.sf.shape[-1] == n > 1
    sf_spec = P(*([None] * (pw.sf.ndim - 1)), n_axis) if split_sf else P()

    def per_shard(xs, codes, sf):
        kl = xs.shape[1] if k_axis is not None else k
        local = dataclasses.replace(
            pw, codes=codes, sf=sf, shape=(kl, codes.shape[1]), split=None, mesh=None
        )
        with jax.named_scope("elp_bsd_matmul"):
            out = _quantized_matmul(
                xs, local, impl=impl, out_dtype=F32 if k_axis else out_dtype, **kw
            )
        if k_axis is None:
            return out
        with jax.named_scope(COLLECTIVE_SCOPE):
            out = jax.lax.psum(out, k_axis)
        return out.astype(out_dtype)

    out = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(rows, k_axis), P(k_axis, n_axis), sf_spec),
        out_specs=P(rows, n_axis),
        check_vma=False,  # the Pallas kernels' outputs state no varying axes
    )(x2, pw.codes, pw.sf)
    return out.reshape(*lead, n)


@functools.partial(
    jax.jit,
    static_argnames=(
        "impl",
        "block_m",
        "block_n",
        "block_k",
        "block_sizes",
        "out_dtype",
        "interpret",
    ),
)
def _quantized_matmul(
    x: Array,
    pw: PackedWeight,
    *,
    impl: str = "pallas",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    block_sizes: tuple[int, int, int] | None = None,
    out_dtype=None,
    interpret: bool | None = None,
) -> Array:
    """``x[..., K] @ dequant(pw)[K, N]`` with fused in-VMEM decode.

    When the weight carries a calibrated static activation quantizer
    (``act_scale``/``act_bits`` aux data), the input is fake-quantized
    against that compile-time constant first — the serve path's
    zero-reduction activation quantization.

    ``impl`` picks the datapath: ``"pallas"`` (tiled decode+matmul
    kernel), ``"pallas_fused"`` (decode-step kernel — shift-add decode,
    whole-M strip; lowers to the single-pass XLA shift-add form on
    non-TPU backends, bit-identical to ``"xla"``), ``"xla"``
    (dequantize-then-matmul fallback), or ``"auto"`` for the kernel and
    tiles :func:`select_impl` derives from the shape.

    ``block_sizes`` overrides the individual ``block_*`` args, and the
    rule's tiles under ``"auto"``, with a ``(block_m, block_n, block_k)``
    tuple. Shapes are static under jit, so the rule runs at trace time
    and costs nothing per call.
    """
    if pw.act_scale is not None:
        from repro.core.quantize import fake_quant_uniform

        x = fake_quant_uniform(x, pw.act_bits or 8, pw.act_scale)
    k, n = pw.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m0 = x2.shape[0]
    out_dtype = out_dtype or x.dtype
    if impl == "auto":
        impl, blocks, _ = select_impl(m0, k, n, nibble=pw.nibble, stacked=pw.codes.ndim != 2)
        block_sizes = block_sizes or blocks
    # Validate block_sizes for every impl (the xla path ignores blocks,
    # but a typo'd value or an odd nibble block_k must not succeed there
    # and only blow up later on the TPU path).
    if block_sizes is not None:
        if not (isinstance(block_sizes, tuple) and len(block_sizes) == 3):
            raise ValueError(
                f"block_sizes must be a (block_m, block_n, block_k) tuple or None; "
                f"got {block_sizes!r}"
            )
        block_m, block_n, block_k = block_sizes
    if pw.nibble and block_k % 2 != 0:
        raise ValueError(
            f"nibble-packed weights need an even block_k (two codes per byte along K); "
            f"got block_k={block_k} for weight {pw.shape} fmt={pw.fmt_name}"
        )
    if impl == "xla":
        out = jnp.dot(
            x2.astype(jnp.float32), dequantize(pw), preferred_element_type=jnp.float32
        ).astype(out_dtype)
        return out.reshape(*lead, n)
    if impl == "pallas_fused":
        if pw.codes.ndim != 2:
            raise ValueError(
                "pallas_fused path takes a single [K, N] weight; use impl='xla' for stacks"
            )
        if interpret is not True and jax.default_backend() != "tpu":
            # Single-pass XLA form of the same datapath: shift-add decode
            # feeding one dot, no select-chain/sign-multiply intermediates.
            # Bit-identical to impl="xla" (the decoders agree bit-for-bit)
            # and measurably faster on CPU decode GEMMs (DESIGN.md §14).
            out = jnp.dot(
                x2.astype(jnp.float32),
                dequantize_shift_add(pw),
                preferred_element_type=jnp.float32,
            ).astype(out_dtype)
            return out.reshape(*lead, n)
        x2 = _pad_to(x2, 1, block_k)
        krow = block_k // 2 if pw.nibble else block_k
        codes = _pad_to(_pad_to(pw.codes, 0, krow), 1, block_n)
        per_channel = pw.sf.size > 1
        sf_kernel = jnp.ones((), jnp.float32) if per_channel else pw.sf
        out = fused_decode_matmul(
            x2,
            codes,
            sf_kernel,
            pw.fmt,
            nibble=pw.nibble,
            block_n=block_n,
            block_k=block_k,
            out_dtype=jnp.float32 if per_channel else out_dtype,
            interpret=interpret,
        )
        out = out[:, :n]
        if per_channel:
            out = (out * pw.sf.reshape(1, n)).astype(out_dtype)
        return out.reshape(*lead, n)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")
    if pw.codes.ndim != 2:
        raise ValueError("pallas path takes a single [K, N] weight; use impl='xla' for stacks")
    # Pad M and K on activations (zero activations contribute zero even
    # against garbage codes — including the nibble pad row); pad N on
    # codes and slice the output.
    x2 = _pad_to(_pad_to(x2, 0, block_m), 1, block_k)
    codes = pw.codes
    krow = block_k // 2 if pw.nibble else block_k
    codes = _pad_to(_pad_to(codes, 0, krow), 1, block_n)
    # Per-channel sf scales output columns, so it factors out of the
    # matmul: run the kernel unscaled and apply sf on the sliced output.
    per_channel = pw.sf.size > 1
    sf_kernel = jnp.ones((), jnp.float32) if per_channel else pw.sf
    out = elp_bsd_matmul(
        x2,
        codes,
        sf_kernel,
        pw.fmt,
        nibble=pw.nibble,
        block_m=block_m,
        block_n=block_n,
        block_k=block_k,
        out_dtype=jnp.float32 if per_channel else out_dtype,
        interpret=interpret,
    )
    out = out[:m0, :n]
    if per_channel:
        out = (out * pw.sf.reshape(1, n)).astype(out_dtype)
    return out.reshape(*lead, n)
