"""Kernel-backend selection and the corpus-norm cache (one knob, one cache).

Every wave-scoring and pool-merge call site in the engine dispatches through
a single :class:`Backend` value instead of the historical
``use_pallas`` / ``use_fused_merge`` / ``interpret`` boolean triple:

* ``"ref"``        — the frozen ``repro.kernels.ref`` oracle through XLA
  (gather-then-reduce distances, stable merges). The correctness contract:
  every other backend is tested against it. This is the **default** at every
  public entry point, so existing bit-exact parity guarantees (batched vs
  legacy vs sharded) are untouched unless a caller opts in.
* ``"xla_matmul"`` — MXU/BLAS-form distances over the corpus-norm cache:
  ``d(x, q) = ‖x‖² − 2·⟨x, q⟩ + ‖q‖²`` (resp. plain dot products for
  ip/cosine) with ``‖x‖²`` (and inverse norms for cosine) precomputed once
  per corpus in a :class:`CorpusView`. The inner reduce becomes a
  ``dot_general`` that hits BLAS on CPU and the MXU on TPU, and the per-wave
  flop count drops by ~⅓ (the subtract-square pass disappears). Same math
  as the oracle up to fp association — *tolerance* parity, not bit parity.
* ``"pallas"``     — the fused TPU kernels (``repro.kernels.l2_topk``):
  matmul-form scoring tile with the norm cache as an extra operand, plus
  the payload-carrying bitonic pool merge (lane-width padded).
  ``"pallas-interpret"`` is the same kernels under ``interpret=True`` — the
  CPU-testable form used by the parity grid and CI.
* ``"auto"``       — ``"pallas"`` when a TPU is present, else
  ``"xla_matmul"``. The deployment knob: resolves against the runtime's
  device set, never silently at import time.

The legacy boolean kwargs are kept as deprecated shims: passing any of them
explicitly still works (mapped onto the equivalent Backend) and emits a
``DeprecationWarning`` exactly once per (call-site function, kwarg) pair.

**Corpus-norm cache invalidation**: a :class:`CorpusView` is an immutable
snapshot of ``(rows, ‖x‖², 1/‖x‖)``. jax arrays cannot be mutated in place,
so "mutating the corpus" always means producing a *new* array — build a new
view with :func:`as_corpus_view` at that point; holding the old view against
a new corpus is the only way to get stale norms, and nothing in the engine
does it (the serving engine builds its view once per engine lifetime,
alongside the index, which is itself corpus-immutable).

**Quantized residency**: ``as_corpus_view(corpus, quantize="int8"|"fp8")``
stores the resident rows quantized — int8 with a per-row affine
scale/zero-point pair, or fp8 (e4m3 by default, ``"fp8_e5m2"`` where the
jax dtype exists) with a per-row scale — while the norm cache is computed
over the *dequantized* rows, so the matmul-form expansion stays exact
against the one dequant semantics (``repro.kernels.ref.dequant_rows_ref``).
The paper's framing makes this a principled lever: the proxy stage may be
lossy (quantization error folds into the C-approximation factor) while the
ground-truth stage stays exact, so the resident corpus shrinks 4x vs f32
(2x vs bf16) at dim 256 and the gather-bound wave moves proportionally
fewer HBM bytes. Quantization happens exactly once, at view build; views
stay immutable snapshots, and ``"auto"`` never silently quantizes — a
quantized view only ever exists because a caller asked for one.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

Array = jax.Array

BACKEND_NAMES = ("ref", "xla_matmul", "pallas")

#: quantized-residency modes accepted by :func:`as_corpus_view` (and the
#: ``quantize=`` knob on the entry points). "fp8" is e4m3; "fp8_e5m2" is the
#: wide-exponent variant. Modes whose jax dtype is missing in this build are
#: rejected at view-build time with a clear error instead of at trace time.
QUANTIZE_MODES = ("int8", "fp8", "fp8_e5m2")

# fp8 dtype table, gated on availability in the installed jax/ml_dtypes
_FP8_DTYPES: dict[str, object] = {}
if hasattr(jnp, "float8_e4m3fn"):
    _FP8_DTYPES["fp8"] = jnp.float8_e4m3fn
if hasattr(jnp, "float8_e5m2"):
    _FP8_DTYPES["fp8_e5m2"] = jnp.float8_e5m2

#: epsilon under the cosine rsqrt — must match ``repro.kernels.ref`` so the
#: matmul form agrees with the oracle on (near-)zero rows: a zero row (e.g.
#: uneven-shard padding) carries ``‖x‖² = 0`` and a *finite* inverse norm,
#: so its cosine distance is exactly 1.0 in every backend, never NaN.
NORM_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class Backend:
    """Resolved kernel-dispatch choice (hashable — safe as a jit static).

    ``fused_merge`` overrides the merge route only: ``None`` (default)
    derives it from the backend name (the bitonic kernel iff ``pallas``);
    the legacy ``use_fused_merge`` shim maps onto it.

    ``quantize`` asks the scoring path to hold the corpus in quantized
    residency (:data:`QUANTIZE_MODES`): entry points that build the view
    build it quantized, and a prebuilt view handed in must carry the same
    mode (mismatches raise — a quantized view is never silently
    requantized or promoted). ``None`` scores whatever residency the view
    already has, so prebuilt quantized views flow through every backend
    without restating the mode at each call site.
    """

    name: str  # "ref" | "xla_matmul" | "pallas"
    interpret: bool = False  # run Pallas bodies in interpret mode (CPU CI)
    fused_merge: bool | None = None
    quantize: str | None = None  # None | "int8" | "fp8" | "fp8_e5m2"

    def __post_init__(self):
        if self.name not in BACKEND_NAMES:
            raise ValueError(
                f"backend must be one of {BACKEND_NAMES}, got {self.name!r}")
        if self.quantize is not None and self.quantize not in QUANTIZE_MODES:
            raise ValueError(
                f"quantize must be None or one of {QUANTIZE_MODES}, "
                f"got {self.quantize!r}")

    @property
    def use_pallas(self) -> bool:
        return self.name == "pallas"

    @property
    def matmul(self) -> bool:
        """Score in matmul form over the corpus-norm cache?"""
        return self.name in ("xla_matmul", "pallas")

    @property
    def merge_pallas(self) -> bool:
        """Route pool merges through the Pallas bitonic network?"""
        if self.fused_merge is not None:
            return self.fused_merge
        return self.name == "pallas"


REF = Backend("ref")


def _tpu_present() -> bool:
    # a backend that fails to initialise raises here: that is a broken
    # installation, not an answer of "no TPU"
    return any(d.platform == "tpu" for d in jax.devices())


# one DeprecationWarning per (function, kwarg) pair for the whole process —
# the shims must nudge, not spam a hot loop's logs
_warned: set[tuple[str, str]] = set()


def warn_deprecated_knob(func: str, kwarg: str) -> None:
    key = (func, kwarg)
    if key in _warned:
        return
    _warned.add(key)
    warnings.warn(
        f"{func}(..., {kwarg}=) is deprecated; pass backend= instead "
        "('ref' | 'xla_matmul' | 'pallas' | 'pallas-interpret' | 'auto' "
        "or a repro.kernels.Backend)",
        DeprecationWarning, stacklevel=3)


def resolve_backend(
    backend: str | Backend | None = None,
    *,
    use_pallas: bool | None = None,
    use_fused_merge: bool | None = None,
    interpret: bool | None = None,
    quantize: str | None = None,
    default: str = "ref",
    _caller: str = "repro.kernels",
) -> Backend:
    """Normalize the backend knob (or the legacy boolean shims) to a Backend.

    Accepted ``backend`` values: a :class:`Backend`, one of
    ``"ref" | "xla_matmul" | "pallas" | "pallas-interpret" | "auto"``, or
    None. ``"auto"`` resolves against the runtime device set (pallas on
    TPU, xla_matmul otherwise). With ``backend=None`` the legacy kwargs
    decide — each one explicitly passed emits a once-per-call-site
    ``DeprecationWarning`` — and when nothing at all is passed the
    ``default`` (the frozen oracle) is returned.

    ``quantize`` rides along onto the resolved Backend (it composes with
    every name, including ``"auto"`` — auto picks the *execution* path,
    never the residency). Passing both ``quantize=`` and a ``Backend``
    that already carries a different mode raises.
    """
    if backend is not None:
        if isinstance(backend, Backend):
            if quantize is not None and backend.quantize not in (None, quantize):
                raise ValueError(
                    f"{_caller}: quantize={quantize!r} conflicts with "
                    f"backend.quantize={backend.quantize!r}")
            if quantize is not None and backend.quantize is None:
                return dataclasses.replace(backend, quantize=quantize)
            return backend
        if backend == "auto":
            return Backend("pallas" if _tpu_present() else "xla_matmul",
                           quantize=quantize)
        if backend == "pallas-interpret":
            return Backend("pallas", interpret=True, quantize=quantize)
        return Backend(backend, quantize=quantize)
    name = default
    fused = None
    interp = False
    legacy = (use_pallas is not None or use_fused_merge is not None
              or interpret is not None)
    if legacy:
        # the historical kwargs were independent: use_pallas only routed
        # the *scoring* kernels and defaulted the merge to the stable XLA
        # cut (use_fused_merge=False) — so a shimmed call must not derive
        # fused_merge from the backend name the way the new knob does
        fused = bool(use_fused_merge) if use_fused_merge is not None else False
    if use_pallas is not None:
        warn_deprecated_knob(_caller, "use_pallas")
        name = "pallas" if use_pallas else default
    if use_fused_merge is not None:
        warn_deprecated_knob(_caller, "use_fused_merge")
    if interpret is not None:
        warn_deprecated_knob(_caller, "interpret")
        interp = bool(interpret)
    return Backend(name, interpret=interp, fused_merge=fused,
                   quantize=quantize)


class CorpusView(NamedTuple):
    """Immutable corpus snapshot + the per-row norm cache (a pytree).

    ``rows`` keeps the corpus dtype untouched (a bf16/f16 corpus is *not*
    upcast — the cache adds 8 bytes/row of f32 norms, not a second f32
    corpus); ``sq_norms`` is ``‖x_i‖²`` and ``inv_norms`` is
    ``1/√(‖x_i‖² + NORM_EPS)``, both f32. Zero rows (uneven-shard padding)
    carry ``sq_norms == 0`` and a finite ``inv_norms``, so they score 0
    under sqeuclidean-vs-origin and exactly 1.0 under cosine — padding
    never pollutes any metric. Under the corpus mesh the norms shard with
    the rows (same contiguous blocks), so the cache adds nothing to the
    wave's psum traffic.

    **Quantized residency** (``scales is not None``): ``rows`` holds int8
    or fp8 codes and ``scales`` / ``zero_points`` the per-row dequant
    parameters (``zero_points`` is None for the symmetric fp8 modes). The
    norms are computed over the *dequantized* rows, so the matmul-form
    expansion scores the dequantized corpus exactly
    (``ref.dequant_rows_ref`` is the one semantics every backend matches).
    Zero rows quantize to codes that dequantize to exact zeros: norm 0,
    finite inverse norm, cosine exactly 1.0 — uneven-shard padding stays
    inert under quantization too. The dequant parameters shard with the
    rows under the corpus mesh, riding the same contiguous blocks as the
    norm cache.

    See the module docstring for the invalidation contract: views are
    snapshots; a new corpus array needs a new view.
    """

    rows: Array  # (N, dim) — corpus: original dtype, or int8/fp8 codes
    sq_norms: Array  # (N,) f32 ‖x‖² (of the dequantized rows if quantized)
    inv_norms: Array  # (N,) f32 1/√(‖x‖² + NORM_EPS)
    scales: Array | None = None  # (N,) f32 per-row dequant scale
    zero_points: Array | None = None  # (N,) f32 per-row zero point (int8)

    @property
    def n(self) -> int:
        return self.rows.shape[0]

    @property
    def dim(self) -> int:
        return self.rows.shape[1]

    @property
    def quantize(self) -> str | None:
        """The residency mode of this view (a :data:`QUANTIZE_MODES` name)."""
        if self.scales is None:
            return None
        if self.rows.dtype == jnp.int8:
            return "int8"
        for mode, dt in _FP8_DTYPES.items():
            if self.rows.dtype == dt:
                return mode
        raise ValueError(
            f"quantized view with unrecognized rows dtype {self.rows.dtype}")

    @property
    def bytes_per_row(self) -> int:
        """Resident bytes per corpus row (codes + norms + dequant params)."""
        per = self.rows.dtype.itemsize * self.dim
        per += self.sq_norms.dtype.itemsize + self.inv_norms.dtype.itemsize
        if self.scales is not None:
            per += self.scales.dtype.itemsize
        if self.zero_points is not None:
            per += self.zero_points.dtype.itemsize
        return per


def _quantize_rows_int8(rows_f32: Array) -> tuple[Array, Array, Array]:
    """Per-row affine int8: q = clip(round(x/s) + z), dequant (q - z)·s.

    ``s = (max - min) / 255`` with a zero-range guard (constant rows take
    s = 1 and quantize exactly onto their zero point), ``z`` the rounded
    affine zero point. A zero row therefore dequantizes to exact zeros.
    """
    mn = jnp.min(rows_f32, axis=-1)
    mx = jnp.max(rows_f32, axis=-1)
    scale = (mx - mn) / 255.0
    scale = jnp.where(scale > 0.0, scale, 1.0)
    zp = jnp.round(-128.0 - mn / scale)
    q = jnp.clip(jnp.round(rows_f32 / scale[:, None]) + zp[:, None],
                 -128.0, 127.0).astype(jnp.int8)
    return q, scale, zp


def _quantize_rows_fp8(rows_f32: Array, dtype) -> tuple[Array, Array]:
    """Per-row symmetric fp8: q = (x/s).astype(fp8), dequant q·s.

    ``s = max|x| / finfo(dtype).max`` with a zero guard, so each row uses
    the format's full dynamic range and zero rows stay exactly zero (fp8
    represents 0 exactly).
    """
    fmax = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(rows_f32), axis=-1)
    scale = jnp.where(amax > 0.0, amax / fmax, 1.0)
    q = (rows_f32 / scale[:, None]).astype(dtype)
    return q, scale


def as_corpus_view(corpus: Array | CorpusView,
                   quantize: str | None = None) -> CorpusView:
    """Build (or pass through) the norm cache for a corpus.

    Idempotent: a :class:`CorpusView` is returned unchanged, so call sites
    can accept either form and the norms are only ever computed once per
    corpus — build the view *outside* any hot loop and thread it through.

    ``quantize`` selects quantized residency (:data:`QUANTIZE_MODES`):
    rows are stored as int8/fp8 codes with per-row dequant parameters, and
    the norms are computed over the dequantized rows (the lossy proxy the
    scoring paths actually score). Handing in a prebuilt view with a
    *different* mode raises — requantizing an existing view (raw → int8,
    int8 → fp8, ...) is never done silently; build a fresh view from the
    original corpus instead.
    """
    if isinstance(corpus, CorpusView):
        if quantize is not None and corpus.quantize != quantize:
            raise ValueError(
                f"as_corpus_view(quantize={quantize!r}) got a prebuilt view "
                f"with quantize={corpus.quantize!r}; views are immutable "
                "snapshots — build a new view from the original corpus")
        return corpus
    if quantize is None:
        sq = jnp.sum(jnp.square(corpus.astype(jnp.float32)), axis=-1)
        return CorpusView(
            rows=corpus,
            sq_norms=sq,
            inv_norms=jax.lax.rsqrt(sq + NORM_EPS),
        )
    if quantize not in QUANTIZE_MODES:
        raise ValueError(
            f"quantize must be None or one of {QUANTIZE_MODES}, "
            f"got {quantize!r}")
    rows_f32 = corpus.astype(jnp.float32)
    if quantize == "int8":
        q, scale, zp = _quantize_rows_int8(rows_f32)
    else:
        if quantize not in _FP8_DTYPES:
            raise ValueError(
                f"quantize={quantize!r} needs a jax float8 dtype this build "
                f"does not provide (available: {sorted(_FP8_DTYPES)})")
        q, scale = _quantize_rows_fp8(rows_f32, _FP8_DTYPES[quantize])
        zp = None
    # norms over the DEQUANTIZED rows: the matmul expansion then scores the
    # lossy proxy exactly (one dequant semantics: ref.dequant_rows_ref)
    deq = _ref.dequant_rows_ref(q, scale, zp)
    sq = jnp.sum(jnp.square(deq), axis=-1)
    return CorpusView(
        rows=q,
        sq_norms=sq,
        inv_norms=jax.lax.rsqrt(sq + NORM_EPS),
        scales=scale,
        zero_points=zp,
    )


def corpus_rows(corpus: Array | CorpusView) -> Array:
    """The raw (N, dim) rows of either corpus form."""
    return corpus.rows if isinstance(corpus, CorpusView) else corpus
