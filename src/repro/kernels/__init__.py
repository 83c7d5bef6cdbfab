"""Compute kernels for the search hot path — and the backend contract.

Layout
------
* ``ref.py``      — pure-jnp oracles, **frozen**: the mathematical definition
  every other execution path must match. Never edited for speed.
* ``ops.py``      — the jitted dispatch layer every engine call site uses;
  one ``backend=`` knob per op.
* ``backend.py``  — :class:`Backend` / :func:`resolve_backend` and the
  corpus-norm cache (:class:`CorpusView` / :func:`as_corpus_view`).
* ``l2_topk.py``, ``flash_attention.py``, ``embedding_bag.py`` — the Pallas
  TPU kernel bodies.

Backend-selection contract
--------------------------
``backend=`` accepts ``"ref" | "xla_matmul" | "pallas" | "pallas-interpret"
| "auto"`` (or a resolved :class:`Backend`):

* **auto rule** — ``"auto"`` resolves at call time against the runtime
  device set: ``"pallas"`` when a TPU is present, ``"xla_matmul"``
  otherwise. Nothing resolves at import time.
* **default** — every public entry point defaults to ``"ref"``: the engine's
  bit-exactness guarantees (batched == legacy == sharded) are stated against
  the oracle, so the faster forms are opt-in knobs, not silent swaps.
* **oracle guarantee** — ``"ref"`` *is* ``ref.py`` through XLA.
  ``"xla_matmul"`` and ``"pallas"`` score waves in matmul form over the
  norm cache (``‖x‖² − 2⟨x, q⟩ + ‖q‖²``): identical math up to fp
  reassociation, pinned against the oracle by the backend parity grid
  (``tests/test_backend.py``: pool distances within fp tolerance,
  recall@10 identical, at shards {1, 2, 4}) and the interpret-mode kernel
  suite (``tests/test_kernels.py``, a dedicated CI job).
* **norm-cache invalidation** — a :class:`CorpusView` is an immutable
  snapshot of ``(rows, ‖x‖², 1/‖x‖)``; build it once per corpus *outside*
  the hot loop with :func:`as_corpus_view` and thread it through. jax
  arrays cannot be mutated, so "corpus mutation" means a new array — build
  a new view then (requantizing an existing view raises: views never
  change residency silently). Zero padding rows (uneven shards) carry
  norm 0 and a finite inverse norm: they score +inf/ignored like every
  other masked lane and never pollute cosine.
* **quantized residency** — ``as_corpus_view(corpus, quantize="int8" |
  "fp8" | "fp8_e5m2")`` stores the rows as quantization codes with
  per-row dequant parameters (int8: affine scale + zero-point; fp8:
  symmetric scale), 4x less row payload than f32 at any dim. The view is
  then a **lossy proxy**: norms are computed over the dequantized rows,
  and every backend scores exactly that proxy through one dequant
  semantics (``ref.dequant_rows_ref``) — the Pallas tile dequantizes
  in VMEM (scale/zero-point ride the row-metadata operand next to the
  norms), ``xla_matmul`` runs a dequant-then-dot epilogue,
  and ``"ref"`` dispatches the quantized oracles
  (``ref.gather_score_quant_ref``). This is the bi-metric paper's own
  contract: the cheap stage may be lossy (quantization error folds into
  the C-approximation factor), so quantization is only ever applied to
  proxy corpora — ``bimetric_search``/``BiMetricEngine`` never quantize
  the ground-truth stage, and ``"auto"`` never silently quantizes:
  residency is the caller's explicit ``quantize=`` (or prebuilt-view)
  choice, orthogonal to the execution-path knob. Parity is pinned by
  ``tests/test_quantize.py`` (round-trip bounds, backend × metric ×
  shard grid, recall@10 at matched quota).
* **deprecated shims** — the historical ``use_pallas`` /
  ``use_fused_merge`` / ``interpret`` boolean kwargs still work and map
  onto the equivalent ``Backend``, emitting one ``DeprecationWarning`` per
  call site.
"""
from repro.kernels.backend import Backend, CorpusView  # noqa: F401
from repro.kernels.backend import as_corpus_view, resolve_backend  # noqa: F401
