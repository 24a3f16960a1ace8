"""Quantization schemes and model adapters behind :mod:`repro.api`.

Two small abstractions make the one-front-door façade possible:

* :class:`QuantScheme` — a frozen, hashable, JSON-round-trippable
  description of an entire CoNLoCNN conversion: weight format and
  scale granularity (Sec. IV/V), Algorithm 1 compensation, the
  activation policy (float / dynamic / calibrated-static, DESIGN.md
  §6 + Sec. V step 1), bias folding, kernel block sizes, and the
  accuracy-constraint search knobs. Fixed-point deployment work
  (Goyal & Vanschoren 2021; Spingarn-Eliezer et al. 2022) stresses
  that this configuration must be a first-class reproducible object —
  the scheme is exactly that, and it rides through ``jax.jit`` static
  arguments and the saved artifact manifest unchanged.

* :class:`ModelAdapter` — the protocol that puts ``CnnSpec`` and
  ``ArchConfig`` models behind one surface (init / forward / tap /
  weight-group-axes / calibrate / pack / generate), so the façade, the
  bench workloads, and the Sec. V CBW_A search stop special-casing
  model type. :class:`CnnAdapter` and :class:`LmAdapter` are the two
  shipped implementations; anything structurally compatible passes
  :func:`as_adapter` too.

The packing tree-walks live here as :func:`pack_cnn_params` /
:func:`pack_lm_params` — this is their one home; the old entry points
(``models.cnn.quantize_params``,
``runtime.quantized_params.quantize_params_for_serving``) are
deprecated wrappers that delegate into these.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, ClassVar, Mapping, Protocol

import jax

from repro.calib.policy import CLIP_MODES, CalibrationTable
from repro.configs.base import ArchConfig
from repro.core.elp_bsd import ElpBsdFormat, resolve_format
from repro.models.cnn import CnnSpec, Conv, Fc, Pool
from repro.runtime.quantized_params import (
    ACT_SITE_BY_LEAF,
    QUANTIZABLE,
    quantize_stacked,
)

Array = jax.Array

ACT_POLICIES = ("float", "dynamic", "static")
GRANULARITIES = (None, "per_tensor", "per_channel", "per_slice")


# ---------------------------------------------------------------------------
# QuantScheme
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class QuantScheme:
    """A complete conversion configuration (one object, paper-mapped).

    Weight side (Sec. IV + V steps 2–4, Algorithm 1):
      fmt: ELP_BSD format — preset name, ``elp4``/``elp8`` alias, or an
        :class:`ElpBsdFormat` (normalized to its preset name).
      granularity: scale-factor granularity; ``None`` picks the model
        default (``per_tensor`` for CNNs, ``per_slice`` for stacked LM
        matmuls — DESIGN.md §3 table).
      nibble: force/disable nibble packing (``None`` = 4-bit formats
        pack two codes per byte).
      compensate: Algorithm 1 convert-time error compensation.

    Activation side (Sec. V step 1 + DESIGN.md §6):
      act: ``"float"`` (no activation quantization), ``"dynamic"``
        (uniform fixed-point with a runtime per-tensor range — the
        paper's FP implementation), or ``"static"`` (calibrated
        compile-time scales; requires ``calib_data`` at
        :func:`repro.api.quantize` time).
      act_bits: activation bit-width (``None`` = 8, or whatever the
        CBW_A search settles on when an ``eval_fn`` is supplied).
      clip / pct / rho_threshold: calibration policy knobs
        (percentile clipping, correlation gate).
      fold_bias: fold ``W @ E[eps]`` activation compensation into
        consumer biases at convert time (CNN static path).

    Execution:
      block_sizes: kernel tiling for the packed matmul/conv paths —
        ``None`` (the kernels' defaults, and the shape rule's tiles under
        ``impl="auto"``) or an explicit ``(block_m, block_n, block_k)``.

    Accuracy-constraint search (Sec. V steps 1+5; active when
    :func:`repro.api.quantize` receives an ``eval_fn``):
      ac: maximum tolerated accuracy drop.
      bw_max / bw_min: activation bit-width search range.

    Speculative serving (LMs; DESIGN.md §10):
      spec_verify: the VERIFY tier — ``"float"`` (the unquantized
        checkpoint) or an ELP_BSD format name strictly wider than
        ``fmt``. When set, :func:`repro.api.quantize` packs a second
        tier of the same checkpoint and ``QuantizedModel.generate`` /
        ``serve`` decode self-speculatively: ``fmt`` (the aggressive
        low-bit artifact) drafts, ``spec_verify`` verifies and defines
        the output. Built with :meth:`QuantScheme.speculative`.
      spec_k: verify width W (draft steps per round); >= 2 when
        ``spec_verify`` is set, else 0.
      spec_draft: where drafts come from — ``"model"`` (the ``fmt``
        tier's own forward drafts token by token; the paper-faithful
        mode, fastest where low-bit forwards are genuinely cheaper than
        the verify tier's) or ``"ngram"`` (token-recycling prompt
        lookup: the engine replays, from its own verified output
        history, which token followed each token — drafting costs no
        model forward at all, so a round is ONE wide verify dispatch;
        the fast mode on dispatch-overhead-bound hosts like CPU CI).
        Either way the verify tier defines the output, so the served
        stream is token-identical regardless of drafter quality.
    """

    fmt: str = "elp_bsd_a4"
    granularity: str | None = None
    nibble: bool | None = None
    compensate: bool = True
    act: str = "float"
    act_bits: int | None = None
    clip: str = "percentile"
    pct: float = 99.9
    rho_threshold: float = 0.25
    fold_bias: bool = True
    block_sizes: tuple[int, int, int] | None = None
    ac: float = 0.01
    bw_max: int = 8
    bw_min: int = 4
    spec_verify: str | None = None
    spec_k: int = 0
    spec_draft: str = "model"

    def __post_init__(self) -> None:
        object.__setattr__(self, "fmt", resolve_format(self.fmt).name)
        if (self.spec_verify is None) != (self.spec_k == 0):
            raise ValueError(
                "speculative schemes set BOTH spec_verify (the verify tier) and "
                "spec_k (the verify width), or neither — use QuantScheme.speculative()"
            )
        if self.spec_verify is not None:
            if self.spec_k < 2:
                raise ValueError(
                    f"spec_k is the verify width: need >= 2, got {self.spec_k}"
                )
            if self.spec_verify != "float":
                object.__setattr__(
                    self, "spec_verify", resolve_format(self.spec_verify).name
                )
        if self.spec_draft not in ("model", "ngram"):
            raise ValueError(
                f'spec_draft must be "model" or "ngram", got {self.spec_draft!r}'
            )
        if self.act not in ACT_POLICIES:
            raise ValueError(f"act must be one of {ACT_POLICIES}, got {self.act!r}")
        if self.granularity not in GRANULARITIES:
            raise ValueError(
                f"granularity must be one of {GRANULARITIES}, got {self.granularity!r}"
            )
        if self.clip not in CLIP_MODES:
            raise ValueError(f"clip must be one of {CLIP_MODES}, got {self.clip!r}")
        bs = self.block_sizes
        if isinstance(bs, list):
            bs = tuple(bs)
            object.__setattr__(self, "block_sizes", bs)
        ok = bs is None or (
            isinstance(bs, tuple) and len(bs) == 3 and all(isinstance(b, int) for b in bs)
        )
        if not ok:
            raise ValueError(
                f"block_sizes must be None or a (block_m, block_n, block_k) tuple; got "
                f"{self.block_sizes!r} (the tiles under impl='auto' come from the shape "
                f"rule, kernels/ops.select_impl)"
            )
        if self.act_bits is not None and self.act_bits < 2:
            raise ValueError(f"act_bits must be >= 2, got {self.act_bits}")
        if not 2 <= self.bw_min <= self.bw_max:
            raise ValueError(
                f"need 2 <= bw_min <= bw_max, got bw_min={self.bw_min} bw_max={self.bw_max}"
            )

    @classmethod
    def speculative(
        cls,
        draft: str = "elp_bsd_a4",
        K: int = 4,
        verify: str = "float",
        drafter: str = "model",
        **kw,
    ) -> "QuantScheme":
        """A self-speculative serving scheme (DESIGN.md §10).

        ``draft`` is the scheme's ``fmt`` — the aggressively quantized
        tier that drafts ``K - 1`` tokens per round; ``verify``
        (``"float"`` or a wider ELP format) checks each run in one
        ``K``-wide forward and defines the served output. ``drafter``
        picks the draft source (``"model"``: the ``fmt`` tier decodes
        the drafts; ``"ngram"``: token-recycling prompt lookup — no
        draft forwards at all). Any other :class:`QuantScheme` field
        passes through ``**kw``.
        """
        return cls(
            fmt=draft, spec_verify=verify, spec_k=int(K), spec_draft=drafter, **kw
        )

    @property
    def format(self) -> ElpBsdFormat:
        return resolve_format(self.fmt)

    def resolved_act_bits(self) -> int | None:
        """The activation bit-width the scheme implies (None = float)."""
        if self.act == "float":
            return None
        return self.act_bits if self.act_bits is not None else 8

    # -- persistence (artifact manifest) ------------------------------------
    def to_json(self) -> dict:
        doc = dataclasses.asdict(self)
        if isinstance(doc["block_sizes"], tuple):
            doc["block_sizes"] = list(doc["block_sizes"])
        return doc

    @classmethod
    def from_json(cls, doc: Mapping[str, Any]) -> "QuantScheme":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(doc) - known
        if unknown:
            raise ValueError(f"unknown QuantScheme fields {sorted(unknown)}")
        kw = dict(doc)
        if isinstance(kw.get("block_sizes"), list):
            kw["block_sizes"] = tuple(kw["block_sizes"])
        return cls(**kw)


# ---------------------------------------------------------------------------
# Packing walks (the one home; legacy entry points delegate here)
# ---------------------------------------------------------------------------
def pack_cnn_params(
    params: dict[str, Array],
    fmt: ElpBsdFormat | str,
    *,
    compensate: bool = True,
    granularity: str = "per_tensor",
    nibble: bool | None = None,
) -> dict[str, Array]:
    """Pack every conv/fc weight as a PackedWeight (Sec. V + Alg. 1).

    Biases stay in the model dtype (negligible bytes, accuracy-critical
    — same policy as the LM serve path, DESIGN.md §4). The returned
    pytree drops into :func:`repro.models.cnn.forward`, which then runs
    end-to-end on ELP_BSD codes.
    """
    from repro.kernels.ops import pack_conv_weight, pack_weight

    fmt = resolve_format(fmt)
    out: dict[str, Array] = {}
    for name, w in params.items():
        if name.endswith("_w") and w.ndim == 4:
            out[name] = pack_conv_weight(
                w, fmt, compensate=compensate, granularity=granularity, nibble=nibble
            )[0]
        elif name.endswith("_w") and w.ndim == 2:
            out[name] = pack_weight(
                w, fmt, compensate=compensate, granularity=granularity, nibble=nibble
            )[0]
        else:
            out[name] = w
    return out


def _leaf_name(path) -> str | None:
    """Innermost mapping key along a pytree path (the leaf's name)."""
    for e in reversed(path):
        if hasattr(e, "key"):
            return str(e.key)
    return None


def stamp_lm_act(packed: Any, calib: CalibrationTable) -> Any:
    """Stamp static activation quantizers onto a packed LM tree.

    Each PackedWeight gets the scale of the tap site measuring *its
    input* distribution: the leaf's own site when the table carries
    one, else :data:`~repro.runtime.quantized_params.ACT_SITE_BY_LEAF`
    (post-norm ``attn_in``/``ffn_in``, the ``attn_mix`` output mix, the
    ``ffn_hidden`` intermediate). ``quantized_matmul`` then quantizes
    activations against compile-time constants — the decode hot path
    runs zero range reductions (DESIGN.md §6). Leaves without a
    measured site stay without activation quantization rather than
    getting a wrong-distribution scale.
    """
    from repro.kernels.ops import PackedWeight

    def visit(path, leaf):
        if isinstance(leaf, PackedWeight):
            name = _leaf_name(path)
            sc = calib.lookup(name, default=ACT_SITE_BY_LEAF.get(name))
            if sc is not None:
                return dataclasses.replace(leaf, act_scale=sc.amax, act_bits=sc.bits)
        return leaf

    return jax.tree_util.tree_map_with_path(
        visit, packed, is_leaf=lambda l: isinstance(l, PackedWeight)
    )


def pack_lm_params(
    params: Any,
    cfg: ArchConfig,
    fmt: ElpBsdFormat | str,
    *,
    compensate: bool = True,
    calib: CalibrationTable | None = None,
) -> Any:
    """Replace every quantizable matmul leaf with a PackedWeight.

    ``calib`` (e.g. from ``calib.calibrate_lm``) additionally runs
    :func:`stamp_lm_act`, baking static activation quantizers into the
    packed leaves.
    """
    del cfg  # the walk is name-driven; cfg kept for adapter symmetry
    fmt = resolve_format(fmt)
    packed = jax.tree_util.tree_map_with_path(
        lambda path, leaf: _pack_lm_leaf(path, leaf, fmt, compensate), params
    )
    return stamp_lm_act(packed, calib) if calib is not None else packed


def _pack_lm_leaf(path, leaf, fmt: ElpBsdFormat, compensate: bool):
    if _leaf_name(path) in QUANTIZABLE and leaf.ndim >= 2:
        return quantize_stacked(leaf, fmt, compensate=compensate)
    return leaf


def init_packed_lm_params(
    cfg: ArchConfig, key: Array, fmt: ElpBsdFormat | str, *, compensate: bool = True
) -> Any:
    """``pack_lm_params(init_params(cfg, key), cfg, fmt)``, one leaf at a time.

    Each float leaf is drawn by a jit of the whole initializer that
    returns only that leaf (XLA drops the rest, so the values equal the
    full initializer's), packed, and released before the next is drawn.
    That is how a model whose float tree exceeds device memory is made
    on one chip: qwen3_8b's tree is 16.4 GB in bf16, its largest leaf
    3.6 GB, and its elp4 packed tree 6.0 GB.
    """
    from repro.models import get_model

    init = get_model(cfg).init_params
    fmt = resolve_format(fmt)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda k: init(cfg, k), key)
    )
    draw = jax.jit(lambda k, i: jax.tree.leaves(init(cfg, k))[i], static_argnums=1)
    leaves = []
    for i, (path, _) in enumerate(paths):
        # waiting here keeps one float leaf live: an async draw of the
        # next leaf would allocate it while this one is still packing
        leaves.append(jax.block_until_ready(_pack_lm_leaf(path, draw(key, i), fmt, compensate)))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# ModelAdapter protocol + the two shipped adapters
# ---------------------------------------------------------------------------
class ModelAdapter(Protocol):
    """What the façade needs from a model family (structural typing).

    ``weights_map`` returns ``(flat, group_axes, skip, rebuild)``: a
    name-keyed weight map plus Algorithm 1 group axes (the Sec. V
    methodology contract), the names left at full precision, and a
    callable rebuilding the native params tree from a same-keyed map —
    that quartet is what lets ``run_methodology``'s CBW_A search drive
    any model without knowing its pytree shape.
    """

    kind: str

    def init_params(self, key: Array) -> Any: ...

    def forward(self, params: Any, x: Any, **kw) -> Array: ...

    def tapped_forward(self, params: Any) -> Callable[[Any], dict[str, Array]]: ...

    def weights_map(
        self, params: Any
    ) -> tuple[dict[str, Array], dict[str, tuple[int, ...]], tuple[str, ...], Callable]: ...

    def calibrate(
        self, params: Any, calib_data: Any, scheme: QuantScheme
    ) -> tuple[CalibrationTable, Any]: ...

    def pack(
        self, params: Any, scheme: QuantScheme, table: CalibrationTable | None = None
    ) -> Any: ...

    def stamp_act(self, packed: Any, table: CalibrationTable) -> Any: ...

    def generate(self, params: Any, batch: Any, max_new_tokens: int, **kw) -> Array: ...

    def serve(self, params: Any, requests: Any, **kw) -> list: ...

    def model_json(self) -> dict: ...


@dataclasses.dataclass(frozen=True)
class CnnAdapter:
    """CNN families (AlexNet/VGG + minis) behind the adapter protocol."""

    spec: CnnSpec
    kind: ClassVar[str] = "cnn"

    def init_params(self, key: Array) -> dict[str, Array]:
        from repro.models import cnn

        return cnn.init_params(self.spec, key)

    def forward(
        self,
        params: dict[str, Array],
        x: Array,
        *,
        calib: CalibrationTable | None = None,
        act_bits: int | None = None,
        impl: str = "auto",
        block_sizes=None,
        interpret: bool | None = None,
    ) -> Array:
        from repro.models import cnn

        return cnn.forward(
            params,
            self.spec,
            x,
            act_bits,
            calib=calib,
            impl=impl,
            block_sizes=block_sizes,
            interpret=interpret,
        )

    def tapped_forward(self, params: dict[str, Array]):
        from repro.calib.runner import TapCollector
        from repro.models import cnn

        def tapped(x):
            tc = TapCollector()
            cnn.forward(params, self.spec, x, tap=tc)
            return tc.acts

        return tapped

    def weights_map(self, params: dict[str, Array]):
        from repro.models import cnn

        return dict(params), cnn.weight_group_axes(params), (), lambda flat: dict(flat)

    def calibrate(self, params: dict[str, Array], calib_data: Array, scheme: QuantScheme):
        from repro.calib.runner import calibrate_cnn

        return calibrate_cnn(
            params,
            self.spec,
            calib_data,
            bits=scheme.resolved_act_bits() or 8,
            clip=scheme.clip,
            pct=scheme.pct,
            rho_threshold=scheme.rho_threshold,
            compensate=scheme.fold_bias,
        )

    def pack(self, params, scheme: QuantScheme, table: CalibrationTable | None = None):
        del table  # CNN static scales live in the forward's calib arg
        return pack_cnn_params(
            params,
            scheme.format,
            compensate=scheme.compensate,
            granularity=scheme.granularity or "per_tensor",
            nibble=scheme.nibble,
        )

    def stamp_act(self, packed, table: CalibrationTable):
        del table  # ditto: the table rides QuantizedModel aux, not the leaves
        return packed

    def generate(self, params, batch, max_new_tokens: int, **kw):
        raise NotImplementedError(
            "CNN models classify — use QuantizedModel.forward(images); "
            "generate() is the LM serve path"
        )

    def serve(self, params, requests, **kw):
        raise NotImplementedError(
            "CNN models classify — use QuantizedModel.forward(images); "
            "serve() is the LM continuous-batching path"
        )

    def model_json(self) -> dict:
        layers = []
        for layer in self.spec.layers:
            if isinstance(layer, Conv):
                layers.append(["conv", layer.ch, layer.k, layer.stride])
            elif isinstance(layer, Pool):
                layers.append(["pool", layer.k, layer.stride])
            elif isinstance(layer, Fc):
                layers.append(["fc", layer.out])
            else:
                raise TypeError(f"unknown CNN layer {layer!r}")
        return {
            "name": self.spec.name,
            "input_hw": self.spec.input_hw,
            "input_ch": self.spec.input_ch,
            "layers": layers,
        }

    @staticmethod
    def model_from_json(doc: Mapping[str, Any]) -> CnnSpec:
        layers = []
        for rec in doc["layers"]:
            tag = rec[0]
            if tag == "conv":
                layers.append(Conv(int(rec[1]), int(rec[2]), int(rec[3])))
            elif tag == "pool":
                layers.append(Pool(int(rec[1]), int(rec[2])))
            elif tag == "fc":
                layers.append(Fc(int(rec[1])))
            else:
                raise ValueError(f"unknown CNN layer tag {tag!r}")
        return CnnSpec(
            name=str(doc["name"]),
            layers=tuple(layers),
            input_hw=int(doc["input_hw"]),
            input_ch=int(doc["input_ch"]),
        )


# Families whose forward supports the activation-tap contract (they run
# through models/transformer.py; ssm/hybrid/encdec have no tap sites yet).
_LM_TAP_FAMILIES = ("dense", "moe", "vlm")


@dataclasses.dataclass(frozen=True)
class LmAdapter:
    """Decoder-LM families (every ``ArchConfig``) behind the protocol.

    ``forward(tokens)`` is a fresh-cache prefill returning logits —
    uniform across families because it goes through the
    :class:`~repro.models.ModelApi` registry. Static activation scales
    are baked into the PackedWeights at pack time, so ``forward`` takes
    no calib argument here.
    """

    cfg: ArchConfig
    kind: ClassVar[str] = "lm"

    def init_params(self, key: Array):
        from repro.models import get_model

        return get_model(self.cfg).init_params(self.cfg, key)

    def _batch(self, x) -> dict[str, Array]:
        return x if isinstance(x, dict) else {"tokens": x}

    def forward(self, params, x, **kw):
        from repro.models import get_model

        api = get_model(self.cfg)
        batch = self._batch(x)
        b, s = batch["tokens"].shape
        cache = api.init_cache(self.cfg, b, s + (self.cfg.frontend_tokens or 0))
        logits, _ = api.prefill(params, self.cfg, batch, cache)
        return logits

    def tapped_forward(self, params):
        from repro.calib.runner import TapCollector
        from repro.models import transformer

        if self.cfg.family not in _LM_TAP_FAMILIES:
            raise NotImplementedError(
                f"activation taps are implemented for {_LM_TAP_FAMILIES} families, "
                f"not {self.cfg.family!r}"
            )

        def tapped(tokens):
            tc = TapCollector()
            transformer.forward(params, self.cfg, tokens, tap=tc)
            return tc.acts

        return tapped

    def weights_map(self, params):
        from repro.checkpoint.manager import _flatten

        wmap, treedef = _flatten(params)
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        names = {k: _leaf_name(path) for k, (path, _) in zip(wmap, flat)}
        group_axes: dict[str, tuple[int, ...]] = {}
        skip: list[str] = []
        for k, leaf in wmap.items():
            if names[k] in QUANTIZABLE and leaf.ndim >= 2:
                group_axes[k] = (leaf.ndim - 2,)
            else:
                skip.append(k)

        def rebuild(wmap2: Mapping[str, Array]):
            return jax.tree_util.tree_unflatten(treedef, [wmap2[k] for k in wmap])

        return wmap, group_axes, tuple(skip), rebuild

    def calibrate(self, params, calib_data, scheme: QuantScheme):
        from repro.calib.runner import calibrate_lm

        if self.cfg.family not in _LM_TAP_FAMILIES:
            raise NotImplementedError(
                f"static activation calibration needs the tap contract, implemented "
                f"for {_LM_TAP_FAMILIES} families — not {self.cfg.family!r}"
            )
        table = calibrate_lm(
            params,
            self.cfg,
            calib_data,
            bits=scheme.resolved_act_bits() or 8,
            clip=scheme.clip,
            pct=scheme.pct,
            rho_threshold=scheme.rho_threshold,
        )
        return table, params

    def pack(self, params, scheme: QuantScheme, table: CalibrationTable | None = None):
        if scheme.granularity not in (None, "per_slice"):
            raise ValueError(
                "stacked LM matmuls quantize per_slice (one SF per layer slice); "
                f"granularity={scheme.granularity!r} has no meaning here"
            )
        if scheme.act == "dynamic":
            raise ValueError(
                'LM serving implements act="float" and act="static" (calibrated '
                "scales baked into the packed weights, DESIGN.md §6); there is no "
                'dynamic-range activation path in the decode graph — use act="static" '
                'with calib_data, or act="float"'
            )
        return pack_lm_params(
            params,
            self.cfg,
            scheme.format,
            compensate=scheme.compensate,
            calib=table,
        )

    def stamp_act(self, packed, table: CalibrationTable):
        return stamp_lm_act(packed, table)

    def generate(
        self,
        params,
        batch,
        max_new_tokens: int,
        *,
        greedy: bool = True,
        key: Array | None = None,
        draft_params: Any = None,
        spec_k: int = 0,
        spec_draft: str = "model",
    ):
        from repro.serve.engine import batch_generate

        return batch_generate(
            self.cfg,
            params,
            self._batch(batch),
            max_new_tokens,
            greedy=greedy,
            key=key,
            draft_params=draft_params,
            spec_k=spec_k,
            spec_draft=spec_draft,
        )

    def serve(
        self,
        params,
        requests,
        *,
        n_slots: int = 4,
        max_len: int | None = None,
        mesh="auto",
        flash_decode: bool = False,
        draft_params: Any = None,
        spec_k: int = 0,
        spec_draft: str = "model",
        metrics: Any = None,
        trace: Any = None,
    ) -> list:
        """Continuous-batching serving through :class:`repro.serve.ServeEngine`.

        ``spec_k`` turns on self-speculative decoding: ``params``
        becomes the verify tier (it defines the output), drafted
        against by ``draft_params`` (``spec_draft="model"``) or the
        engine's token-recycling history (``spec_draft="ngram"``;
        DESIGN.md §10). ``metrics``/``trace`` (an obs ``Registry`` /
        ``TraceLog``) flow through to the engine's instrumentation.
        """
        import numpy as np

        from repro.serve.engine import ServeEngine

        reqs = [(np.asarray(t, np.int32).reshape(-1), int(n)) for t, n in requests]
        if not reqs:
            return []
        if max_len is None:
            max_len = max(t.size + n for t, n in reqs)
        eng = ServeEngine(
            self.cfg,
            params,
            n_slots=min(n_slots, len(reqs)),
            max_len=max_len,
            mesh=mesh,
            flash_decode=flash_decode,
            draft_params=draft_params,
            spec_k=spec_k,
            spec_draft=spec_draft,
            metrics=metrics,
            trace=trace,
        )
        return eng.serve(reqs)

    def model_json(self) -> dict:
        doc = dataclasses.asdict(self.cfg)
        doc["period"] = list(doc["period"])
        return doc

    @staticmethod
    def model_from_json(doc: Mapping[str, Any]) -> ArchConfig:
        known = {f.name for f in dataclasses.fields(ArchConfig)}
        unknown = set(doc) - known
        if unknown:
            raise ValueError(f"unknown ArchConfig fields {sorted(unknown)}")
        kw = dict(doc)
        kw["period"] = tuple(kw.get("period", ()))
        return ArchConfig(**kw)


def as_adapter(model) -> ModelAdapter:
    """Wrap a model description in its adapter (idempotent).

    ``CnnSpec`` → :class:`CnnAdapter`, ``ArchConfig`` →
    :class:`LmAdapter`; objects already satisfying the protocol pass
    through.
    """
    if isinstance(model, CnnSpec):
        return CnnAdapter(model)
    if isinstance(model, ArchConfig):
        return LmAdapter(model)
    if hasattr(model, "kind") and hasattr(model, "pack") and hasattr(model, "forward"):
        return model
    raise TypeError(
        f"cannot adapt {type(model).__name__}: expected a CnnSpec, an ArchConfig, "
        "or a ModelAdapter implementation"
    )
