"""One front door: ``QuantScheme`` → :func:`quantize` → :class:`QuantizedModel`.

CoNLoCNN is a *pipeline* — scale-factor selection → TQL →
nearest-neighbour quantization → Algorithm 1 compensation → ELP_BSD
packing, plus activation calibration and the Sec. V accuracy-constraint
search. This module is the single entry point that drives all of it:

    from repro import api
    from repro.models import cnn

    qm = api.quantize(cnn.ALEXNET_MINI, params,
                      api.QuantScheme(fmt="elp_bsd_a4", act="static"),
                      calib_data=images)
    logits = qm.forward(x)        # packed end-to-end, zero reductions
    qm.save("artifacts/alexnet4b")
    qm2 = api.load("artifacts/alexnet4b")   # bit-identical forward

The same call signature converts decoder LMs (pass an ``ArchConfig``);
``qm.generate(prompts, max_new_tokens=...)`` then serves through the
packed prefill/decode loop. Model families plug in through the
:class:`~repro.api_schemes.ModelAdapter` protocol, so nothing in here
special-cases model type.

:class:`QuantizedModel` is the one serializable artifact of a
conversion: packed params (a registered pytree — it jits, shards, and
``device_put``\\ s like any weight tree), the calibration table, the
scheme that produced it, and a :class:`ConversionReport`. ``save`` /
``load`` round-trip through the fault-tolerant checkpoint manager with
per-leaf SHA-256 checksums; a corrupted artifact raises
:class:`ArtifactError` instead of serving wrong bits.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Any, Callable, Mapping

import jax
import numpy as np

from repro.api_schemes import (
    CnnAdapter,
    LmAdapter,
    ModelAdapter,
    QuantScheme,
    as_adapter,
)
from repro.calib.policy import CalibrationTable
from repro.checkpoint.manager import CheckpointManager, _flatten as _flatten_tree
from repro.core.elp_bsd import resolve_format, storage_bytes
from repro.core.methodology import find_critical_act_bits
from repro.kernels.ops import PackedWeight, dequantize_tree, packed_tree_bytes

__all__ = [
    "ArtifactError",
    "CnnAdapter",
    "ConversionReport",
    "LmAdapter",
    "ModelAdapter",
    "QuantScheme",
    "QuantizedModel",
    "as_adapter",
    "load",
    "quantize",
    "resolve_format",
]

Array = jax.Array

ARTIFACT_VERSION = 1
_MANIFEST = "manifest.json"
_CALIB = "calib.json"
_PARAMS_DIR = "params"
_VERIFY_DIR = "verify_params"


class ArtifactError(ValueError):
    """A saved QuantizedModel is missing, malformed, or corrupted."""


# ---------------------------------------------------------------------------
# Conversion report
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ConversionReport:
    """What a conversion did, in numbers (frozen: rides as jit aux data).

    ``packed_bytes`` counts the runtime storage (one byte per u8 code,
    two nibble codes per byte, float32 scale factors);
    ``encoded_bytes`` is the paper's Table II accounting with codes
    bit-packed at ``bits_per_weight`` (the HBM story an ELP_BSD decoder
    in hardware would see). ``energy_nj`` is the Table II network
    energy estimate (CNNs only — it needs a MAC count).
    """

    fmt: str
    act: str
    act_bits: int | None
    raw_bytes: int
    packed_bytes: int
    packed_weight_bytes: int
    encoded_bytes: int
    baseline_accuracy: float | None = None
    accuracy: float | None = None
    energy_nj: float | None = None

    @property
    def compression(self) -> float:
        return self.raw_bytes / max(self.packed_bytes, 1)

    @property
    def accuracy_loss(self) -> float | None:
        if self.accuracy is None or self.baseline_accuracy is None:
            return None
        return self.baseline_accuracy - self.accuracy

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, doc: Mapping[str, Any]) -> "ConversionReport":
        kw = dict(doc)
        kw.pop("tuned_blocks", None)  # written by artifacts of older versions
        return cls(**kw)


def _encoded_bytes(tree: Any) -> int:
    """Bit-packed (Table II) byte accounting for a packed tree."""
    total = 0
    for leaf in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, PackedWeight)):
        if isinstance(leaf, PackedWeight):
            k, n = leaf.shape
            stack = int(np.prod(leaf.codes.shape[:-2])) if leaf.codes.ndim > 2 else 1
            total += storage_bytes(stack * k * n, leaf.fmt)
            total += int(np.prod(leaf.sf.shape)) * 4
        else:
            total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


# ---------------------------------------------------------------------------
# The façade
# ---------------------------------------------------------------------------
def quantize(
    model,
    params: Any,
    scheme: QuantScheme | None = None,
    *,
    calib_data: Any = None,
    eval_fn: Callable[[Any, Any], float] | None = None,
) -> "QuantizedModel":
    """Run the full CoNLoCNN conversion pipeline on one model.

    Args:
      model: a ``CnnSpec``, an ``ArchConfig``, or any
        :class:`~repro.api_schemes.ModelAdapter`.
      params: the trained float parameter pytree for that model.
      scheme: the :class:`~repro.api_schemes.QuantScheme` (defaults to
        4-bit ELP_BSD weights, Algorithm 1 on, float activations).
      calib_data: stacked calibration batches (leading axis = batch
        index) — required when ``scheme.act == "static"``; images
        ``[n, B, H, W, C]`` for CNNs, token batches ``[n, B, S]`` for
        LMs.
      eval_fn: ``eval_fn(params_tree, act_quant) -> accuracy``.
        Supplying it turns on the Sec. V accuracy-constraint search
        (steps 1 + 5): the critical activation bit-width ``CBW_A`` is
        found within ``scheme.ac``, and the constraint is re-checked on
        the *dequantized packed weights* — numerically exactly what the
        artifact serves (per-slice SFs for LMs included) — walking
        ``CBW_A`` back up on violation. ``act_quant`` is ``None``, an
        int bit-width, or a ``CalibrationTable`` — exactly the
        ``benchmarks.common.make_eval_fn`` contract.

    Internally: calibrate → pack (compensate + fold inside) → Sec. V
    search → activation-scale stamping → block-size resolution, all
    through the model's adapter.
    """
    adapter = as_adapter(model)
    scheme = scheme if scheme is not None else QuantScheme()
    fmt = scheme.format

    table: CalibrationTable | None = None
    work = params
    if scheme.act == "static":
        if calib_data is None:
            raise ValueError(
                'scheme.act == "static" needs calib_data (stacked calibration batches)'
            )
        table, work = adapter.calibrate(params, calib_data, scheme)

    packed = adapter.pack(work, scheme, table)

    # speculative schemes pack a SECOND tier of the same checkpoint: the
    # verify tier ("float" = the calibrated float tree itself) that the
    # serve engine uses to check the low-bit draft's tokens. Report and
    # byte accounting below stay about the draft artifact — that is the
    # paper's product; the verify tier is a serving accelerant's safety
    # net (DESIGN.md §10).
    verify_params = None
    if scheme.spec_k:
        if adapter.kind != "lm":
            raise ValueError(
                "speculative schemes (spec_verify/spec_k) are an LM serving "
                "feature; CNN models classify in one forward"
            )
        if scheme.spec_verify == "float":
            verify_params = work
        else:
            vscheme = dataclasses.replace(
                scheme, fmt=scheme.spec_verify, spec_verify=None, spec_k=0
            )
            verify_params = adapter.pack(work, vscheme, table)

    baseline_acc: float | None = None
    accuracy: float | None = None
    act_bits = scheme.resolved_act_bits()
    if eval_fn is not None:
        # The baseline is the user's trained float model — NOT the
        # bias-folded calibration output, whose compensation only makes
        # sense under activation quantization.
        baseline_acc = eval_fn(params, None)
        deq = dequantize_tree(packed)
        if scheme.act == "float":
            # No activation quantization in serving, so no CBW_A search:
            # just measure what the artifact actually delivers.
            accuracy = eval_fn(deq, None)
        else:
            cbw = find_critical_act_bits(
                eval_fn, params, baseline_acc, scheme.ac, scheme.bw_max, scheme.bw_min,
                calib=table,
            )

            # Step 5 on the real artifact: evaluate the float twin of
            # the packed codes and walk activation precision back up
            # while the constraint is violated.
            def act_quant(bits: int):
                return table.with_bits(bits) if table is not None else bits

            accuracy = eval_fn(deq, act_quant(cbw))
            while baseline_acc - accuracy > scheme.ac and cbw < scheme.bw_max:
                cbw += 1
                accuracy = eval_fn(deq, act_quant(cbw))
            act_bits = cbw
            if table is not None:
                table = table.with_bits(act_bits)
                packed = adapter.stamp_act(packed, table)

    raw_bytes = packed_tree_bytes(params)
    packed_bytes = packed_tree_bytes(packed)
    packed_weight_bytes = packed_tree_bytes(packed, packed_only=True)
    encoded_bytes = _encoded_bytes(packed)
    energy = None
    if adapter.kind == "cnn":
        from repro.core.energy import network_energy_nj

        energy = network_energy_nj(
            adapter.spec.macs(), encoded_bytes, fmt.name, act_bits or 8
        )["total_nj"]
    report = ConversionReport(
        fmt=fmt.name,
        act=scheme.act,
        act_bits=act_bits,
        raw_bytes=raw_bytes,
        packed_bytes=packed_bytes,
        packed_weight_bytes=packed_weight_bytes,
        encoded_bytes=encoded_bytes,
        baseline_accuracy=baseline_acc,
        accuracy=accuracy,
        energy_nj=energy,
    )
    return QuantizedModel(
        packed, adapter, scheme, table=table, report=report, verify_params=verify_params
    )


# ---------------------------------------------------------------------------
# QuantizedModel
# ---------------------------------------------------------------------------
class QuantizedModel:
    """The artifact of a conversion: packed params + everything needed
    to serve and reproduce them.

    A registered pytree: the packed params (plus the optional
    speculative verify tier) are the children, the adapter / scheme /
    table / report ride as hashable aux data — so a QuantizedModel
    passes through ``jax.jit``, ``jax.device_put``, and shard
    annotations whole.

    ``verify_params`` (speculative schemes only) is the second tier of
    the same checkpoint — ``"float"`` or a wider ELP packing — that
    verifies the draft tier's tokens at serve time and *defines* the
    generated output (DESIGN.md §10). ``forward`` keeps running the
    draft tier: that is the artifact the conversion report describes.
    """

    def __init__(
        self,
        params: Any,
        adapter: ModelAdapter,
        scheme: QuantScheme,
        *,
        table: CalibrationTable | None = None,
        report: ConversionReport | None = None,
        verify_params: Any = None,
    ):
        self.params = params
        self.adapter = adapter
        self.scheme = scheme
        self.table = table
        self.report = report
        self.verify_params = verify_params

    @property
    def model(self):
        """The underlying model description (CnnSpec / ArchConfig)."""
        return getattr(self.adapter, "spec", None) or getattr(self.adapter, "cfg", None)

    # -- pytree -------------------------------------------------------------
    def tree_flatten_with_keys(self):
        ga = jax.tree_util.GetAttrKey
        return (
            (ga("params"), self.params),
            (ga("verify_params"), self.verify_params),
        ), (
            self.adapter,
            self.scheme,
            self.table,
            self.report,
        )

    def tree_flatten(self):
        return (self.params, self.verify_params), (
            self.adapter,
            self.scheme,
            self.table,
            self.report,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        adapter, scheme, table, report = aux
        return cls(
            children[0], adapter, scheme, table=table, report=report,
            verify_params=children[1],
        )

    # -- execution ----------------------------------------------------------
    def forward(self, x, *, impl: str | None = None, block_sizes=None, interpret=None) -> Array:
        """Run the packed model: images → logits (CNN) / tokens → logits (LM).

        The scheme's activation policy is applied automatically: static
        schemes embed the calibration table (zero runtime range
        reductions), dynamic schemes quantize per-tensor at the
        resolved ``act_bits``. For CNNs ``impl`` / ``block_sizes`` /
        ``interpret`` override the scheme's kernel execution for this
        call; the LM path picks its own matmul impl inside
        ``models/layers.matmul``, so passing them there is an error
        rather than a silent no-op.
        """
        if self.adapter.kind == "cnn":
            calib = act_bits = None
            if self.scheme.act == "static":
                calib = self.table
            elif self.scheme.act == "dynamic":
                act_bits = (self.report.act_bits if self.report else None) or 8
            return self.adapter.forward(
                self.params,
                x,
                calib=calib,
                act_bits=act_bits,
                impl=impl or "auto",
                block_sizes=self.scheme.block_sizes if block_sizes is None else block_sizes,
                interpret=interpret,
            )
        if impl is not None or block_sizes is not None or interpret is not None:
            raise ValueError(
                "impl/block_sizes/interpret are CNN execution overrides; the LM serve "
                "path selects its matmul impl internally (models/layers.matmul)"
            )
        return self.adapter.forward(self.params, x)

    def generate(self, batch, max_new_tokens: int, *, greedy: bool = True, key=None) -> Array:
        """LM serving: greedy/sampled generation on the packed weights.

        Greedy generation routes through the continuous-batching
        :class:`~repro.serve.ServeEngine` (DESIGN.md §9); sampled
        generation and non-transformer families keep the static
        lockstep loop. Either way the decode step consumes the packed
        leaves directly — codes enter the graph as uint8.

        Speculative artifacts (``scheme.spec_k``) decode
        self-speculatively: the packed draft tier proposes, the verify
        tier checks and defines the output — token-identical to serving
        the verify tier alone, at a higher tokens/sec (DESIGN.md §10).
        """
        if self.scheme.spec_k:
            return self.adapter.generate(
                self.verify_params,
                batch,
                max_new_tokens,
                greedy=greedy,
                key=key,
                draft_params=(
                    self.params if self.scheme.spec_draft == "model" else None
                ),
                spec_k=self.scheme.spec_k,
                spec_draft=self.scheme.spec_draft,
            )
        return self.adapter.generate(
            self.params, batch, max_new_tokens, greedy=greedy, key=key
        )

    def serve(self, requests, *, n_slots: int = 4, max_len: int | None = None,
              mesh="auto", flash_decode: bool = False, metrics=None,
              trace=None) -> list:
        """Continuous-batching LM serving on the packed weights.

        ``requests`` is an iterable of ``(prompt_tokens, max_new_tokens)``
        pairs — prompts may all have different lengths; nothing is padded
        to a batch maximum. They are admitted into ``n_slots`` cache
        slots of one :class:`~repro.serve.ServeEngine` (``mesh="auto"``
        picks an elastic mesh when several devices are visible) and the
        generated tokens come back as a list of int32 arrays in request
        order. ``max_len`` is the per-slot cache capacity (default: the
        largest ``len(prompt) + max_new`` over the requests).

        Speculative artifacts serve draft/verify rounds (see
        :meth:`generate`); output is token-identical to serving the
        verify tier alone.

        ``metrics`` / ``trace`` (an obs
        :class:`~repro.obs.metrics.Registry` /
        :class:`~repro.obs.trace.TraceLog`) enable the engine's
        TTFT/ITL histograms, energy-per-token counters and per-request
        span events (DESIGN.md §11); both default to disabled.
        """
        if self.scheme.spec_k:
            return self.adapter.serve(
                self.verify_params,
                requests,
                n_slots=n_slots,
                max_len=max_len,
                mesh=mesh,
                flash_decode=flash_decode,
                draft_params=(
                    self.params if self.scheme.spec_draft == "model" else None
                ),
                spec_k=self.scheme.spec_k,
                spec_draft=self.scheme.spec_draft,
                metrics=metrics,
                trace=trace,
            )
        return self.adapter.serve(
            self.params,
            requests,
            n_slots=n_slots,
            max_len=max_len,
            mesh=mesh,
            flash_decode=flash_decode,
            metrics=metrics,
            trace=trace,
        )

    # -- persistence --------------------------------------------------------
    def save(self, path: str) -> None:
        """Write the artifact directory (atomic manifest last).

        Layout: ``manifest.json`` (model/scheme/report/tree structure +
        per-leaf SHA-256 checksums), ``params/`` (checkpoint-manager
        step with the packed pytree), ``calib.json`` (calibration
        table, when the scheme is static), ``verify_params/`` (the
        speculative verify tier, when the scheme carries one — its
        structure and checksums ride the manifest under
        ``verify_tree``/``verify_checksums``).
        """
        os.makedirs(path, exist_ok=True)
        flat, _ = _flatten_tree(self.params)
        checks = {k: _leaf_sha256(v) for k, v in flat.items()}
        mgr = CheckpointManager(os.path.join(path, _PARAMS_DIR), keep=1, async_save=False)
        mgr.save(0, self.params)
        if self.table is not None:
            self.table.save(os.path.join(path, _CALIB))
        manifest = {
            "format_version": ARTIFACT_VERSION,
            "kind": self.adapter.kind,
            "model": self.adapter.model_json(),
            "scheme": self.scheme.to_json(),
            "report": self.report.to_json() if self.report is not None else None,
            "tree": _tree_to_json(self.params),
            "checksums": checks,
            "has_calib": self.table is not None,
        }
        if self.verify_params is not None:
            vflat, _ = _flatten_tree(self.verify_params)
            manifest["verify_tree"] = _tree_to_json(self.verify_params)
            manifest["verify_checksums"] = {k: _leaf_sha256(v) for k, v in vflat.items()}
            vmgr = CheckpointManager(
                os.path.join(path, _VERIFY_DIR), keep=1, async_save=False
            )
            vmgr.save(0, self.verify_params)
        tmp = os.path.join(path, _MANIFEST + ".tmp")
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=1)
        os.replace(tmp, os.path.join(path, _MANIFEST))

    @classmethod
    def load(cls, path: str) -> "QuantizedModel":
        """Load and *verify* a saved artifact.

        Any missing file, schema mismatch, undeclared/missing leaf, or
        checksum failure raises :class:`ArtifactError` — a partially
        written or bit-flipped artifact must never serve.
        """
        mf = os.path.join(path, _MANIFEST)
        try:
            with open(mf) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
            raise ArtifactError(f"unreadable QuantizedModel manifest at {mf}: {e}") from e
        if not isinstance(doc, dict) or doc.get("format_version") != ARTIFACT_VERSION:
            raise ArtifactError(
                f"unsupported artifact format_version "
                f"{doc.get('format_version') if isinstance(doc, dict) else doc!r} "
                f"(expected {ARTIFACT_VERSION})"
            )
        for key in ("kind", "model", "scheme", "tree", "checksums"):
            if key not in doc:
                raise ArtifactError(f"manifest missing required key {key!r}")
        try:
            if doc["kind"] == "cnn":
                adapter: ModelAdapter = CnnAdapter(CnnAdapter.model_from_json(doc["model"]))
            elif doc["kind"] == "lm":
                adapter = LmAdapter(LmAdapter.model_from_json(doc["model"]))
            else:
                raise ValueError(f"unknown artifact kind {doc['kind']!r}")
            scheme = QuantScheme.from_json(doc["scheme"])
            example = _tree_from_json(doc["tree"])
        except (TypeError, ValueError, KeyError) as e:
            raise ArtifactError(f"malformed artifact manifest: {e}") from e

        mgr = CheckpointManager(os.path.join(path, _PARAMS_DIR), keep=0, async_save=False)
        restored = mgr.restore_latest(example)
        if restored is None:
            raise ArtifactError(f"params checkpoint under {path!r} is missing or unreadable")
        _, params = restored

        flat, _ = _flatten_tree(params)
        declared = doc["checksums"]
        if set(flat) != set(declared):
            raise ArtifactError(
                f"artifact leaves {sorted(set(flat) ^ set(declared))} do not match "
                "the manifest"
            )
        for k, v in flat.items():
            if _leaf_sha256(v) != declared[k]:
                raise ArtifactError(f"checksum mismatch for leaf {k!r} — artifact corrupted")

        verify_params = None
        if scheme.spec_k:
            # a speculative scheme WITHOUT its verify tier must not load:
            # serving it would silently emit draft-tier tokens
            if "verify_tree" not in doc or "verify_checksums" not in doc:
                raise ArtifactError(
                    "artifact's scheme is speculative but the manifest has no "
                    "verify tier (verify_tree/verify_checksums) — incomplete save"
                )
            try:
                vexample = _tree_from_json(doc["verify_tree"])
            except (TypeError, ValueError, KeyError) as e:
                raise ArtifactError(f"malformed verify tree: {e}") from e
            vmgr = CheckpointManager(
                os.path.join(path, _VERIFY_DIR), keep=0, async_save=False
            )
            vrestored = vmgr.restore_latest(vexample)
            if vrestored is None:
                raise ArtifactError(
                    f"verify-tier checkpoint under {path!r} is missing or unreadable"
                )
            _, verify_params = vrestored
            vflat, _ = _flatten_tree(verify_params)
            vdeclared = doc["verify_checksums"]
            if set(vflat) != set(vdeclared):
                raise ArtifactError(
                    f"verify-tier leaves {sorted(set(vflat) ^ set(vdeclared))} do "
                    "not match the manifest"
                )
            for k, v in vflat.items():
                if _leaf_sha256(v) != vdeclared[k]:
                    raise ArtifactError(
                        f"checksum mismatch for verify leaf {k!r} — artifact corrupted"
                    )

        table = None
        if doc.get("has_calib"):
            try:
                table = CalibrationTable.load(os.path.join(path, _CALIB))
            except (OSError, json.JSONDecodeError, KeyError, TypeError) as e:
                raise ArtifactError(f"calibration table unreadable: {e}") from e
        report = None
        if doc.get("report") is not None:
            try:
                report = ConversionReport.from_json(doc["report"])
            except (TypeError, ValueError, KeyError) as e:
                raise ArtifactError(f"malformed conversion report: {e}") from e
        return cls(
            params, adapter, scheme, table=table, report=report,
            verify_params=verify_params,
        )


jax.tree_util.register_pytree_with_keys_class(QuantizedModel)


def load(path: str) -> QuantizedModel:
    """Module-level alias for :meth:`QuantizedModel.load`."""
    return QuantizedModel.load(path)


# ---------------------------------------------------------------------------
# Artifact plumbing
# ---------------------------------------------------------------------------
def _leaf_sha256(v) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(jax.device_get(v)).tobytes()
    ).hexdigest()


def _tree_to_json(tree: Any):
    """Structure-only description of a params pytree (for the manifest)."""
    if isinstance(tree, Mapping):
        return {"kind": "dict", "items": {str(k): _tree_to_json(v) for k, v in tree.items()}}
    if isinstance(tree, (list, tuple)):
        return {
            "kind": "tuple" if isinstance(tree, tuple) else "list",
            "items": [_tree_to_json(v) for v in tree],
        }
    if isinstance(tree, PackedWeight):
        return {
            "kind": "packed",
            "fmt": tree.fmt_name,
            "nibble": bool(tree.nibble),
            "shape": list(tree.shape),
            "source_shape": list(tree.source_shape) if tree.source_shape else None,
            "act_scale": tree.act_scale,
            "act_bits": tree.act_bits,
        }
    if hasattr(tree, "shape") and hasattr(tree, "dtype"):
        return {"kind": "array", "shape": list(tree.shape), "dtype": str(tree.dtype)}
    raise TypeError(f"cannot serialize pytree node of type {type(tree).__name__}")


def _tree_from_json(doc) -> Any:
    """Rebuild the example pytree (structure + PackedWeight aux data).

    Leaf *values* are placeholders — the checkpoint manager restores the
    stored arrays by path; only the tree structure and PackedWeight aux
    fields come from the manifest.
    """
    if not isinstance(doc, dict) or "kind" not in doc:
        raise ValueError(f"malformed tree node {doc!r}")
    kind = doc["kind"]
    if kind == "dict":
        return {k: _tree_from_json(v) for k, v in doc["items"].items()}
    if kind in ("list", "tuple"):
        items = [_tree_from_json(v) for v in doc["items"]]
        return tuple(items) if kind == "tuple" else items
    if kind == "packed":
        return PackedWeight(
            codes=np.zeros(0, np.uint8),
            sf=np.zeros(0, np.float32),
            fmt_name=str(doc["fmt"]),
            nibble=bool(doc["nibble"]),
            shape=tuple(int(v) for v in doc["shape"]),
            source_shape=(
                tuple(int(v) for v in doc["source_shape"]) if doc.get("source_shape") else None
            ),
            act_scale=doc.get("act_scale"),
            act_bits=doc.get("act_bits"),
        )
    if kind == "array":
        return np.zeros(0, np.float32)
    raise ValueError(f"unknown tree node kind {kind!r}")
