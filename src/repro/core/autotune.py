"""Run-first auto-tuner (paper §VII-D: "run-first auto-tuner ... finds the
optimal format to use on every process").

Given a matrix, convert it to each candidate ``DispatchKey(format, backend)``,
time the jitted SpMV, and return the winner + the full timing table. This is
deliberately measurement-based (not a learned oracle — that is the
Morpheus-Oracle follow-up paper [35]); conversion cost is excluded, matching
the paper's methodology of timing 100 SpMV iterations after setup.

A race records the host spans ``tune.race`` and, per candidate,
``tune.candidate`` (its conversion's ``convert``, ``tune.first_call`` for
the first warm-up call with its trace and compile, ``tune.time`` for the
other calls) and the counter ``tune.calls`` (``repro.core.obs``).

The result carries a ready-to-use ``SparseOperator`` (winning container +
policy preferring the winning backend) — the operator-centric entry point is
``SparseOperator.tune()`` / ``TuneResult.operator``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from . import obs
from .convert import col_tile_for_policy as _col_tile_for_policy
from .convert import container_to_scipy as _container_to_scipy
from .convert import from_dense as _from_dense
from .errors import BackendUnsupportedError
from .operator import DEFAULT_POLICY, ExecutionPolicy, SparseOperator
from .select import DENSE_MAX_BYTES
from .spmv import DispatchKey, available_impls, spmv

DEFAULT_CANDIDATES: Tuple[DispatchKey, ...] = (
    DispatchKey("coo", "plain"), DispatchKey("coo", "pallas"),
    DispatchKey("csr", "plain"), DispatchKey("csr", "pallas"),
    DispatchKey("dia", "plain"), DispatchKey("dia", "pallas"),
    DispatchKey("ell", "plain"), DispatchKey("ell", "pallas"),
    DispatchKey("sell", "plain"), DispatchKey("sell", "pallas"),
    DispatchKey("bsr", "plain"), DispatchKey("bsr", "pallas"),
    DispatchKey("dense", "dense"),
)

#: Formats whose converters take a ``col_tile`` argument (tiled Pallas plans).
_COL_TILED_FORMATS = ("coo", "csr", "dia", "ell", "sell")


@dataclass
class TuneResult:
    format: str
    impl: str
    time_us: float
    matrix: object
    table: Dict[Tuple[str, str], float] = field(default_factory=dict)
    skipped: List[Tuple[str, str, str]] = field(default_factory=list)
    base_policy: Optional[ExecutionPolicy] = None  # limits candidates ran under

    @property
    def key(self) -> DispatchKey:
        return DispatchKey(self.format, self.impl)

    @property
    def operator(self) -> SparseOperator:
        """The tuned matrix as a retargeted SparseOperator: the winning
        backend chain merged into the policy the tuner measured under."""
        base = self.base_policy if self.base_policy is not None else DEFAULT_POLICY
        return SparseOperator(self.matrix, base.preferring(self.impl))

    def __repr__(self):
        return f"TuneResult(format={self.format!r}, impl={self.impl!r}, {self.time_us:.1f}us)"


def _time_call(fn, *args, iters: int = 10, warmup: int = 3) -> float:
    obs.count("tune.calls", warmup + iters)
    with obs.span("tune.first_call"):
        if warmup:
            jax.block_until_ready(fn(*args))
    ts = []
    with obs.span("tune.time"):
        for _ in range(warmup - 1):
            jax.block_until_ready(fn(*args))
        for _ in range(iters):
            t0 = time.perf_counter_ns()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter_ns() - t0)
    return float(np.median(ts)) / 1e3  # us


def _normalize_candidates(candidates) -> Tuple[Tuple[str, str], ...]:
    # DispatchKey is iterable, so both it and (fmt, impl) tuples unpack
    return tuple((fmt, impl) for fmt, impl in candidates)


def structural_skip(s, fmt: str, dia_max_diags: int = 512,
                    ell_max_width_factor: float = 4.0,
                    bsr_min_block_fill: float = 0.125,
                    dense_max_bytes: int = DENSE_MAX_BYTES) -> Optional[str]:
    """Why ``fmt`` should not even be *built* for matrix ``s`` — or ``None``.

    The practical limits Morpheus applies before racing a candidate
    (paper §V calls out DIA's memory blow-up on the FPGA): DIA is skipped
    when the matrix has too many distinct diagonals, ELL when the max row
    width far exceeds the mean (power-law rows pad catastrophically), BSR
    when the 32-edge block fill is so low its zero-padded blocks blow up
    storage, dense when the f32 n x m array would pass ``dense_max_bytes``.
    Shared by the single-matrix tuner below and the per-partition
    distributed tuner, so every tuning path applies identical guards.

    Args:
        s: scipy sparse matrix (any layout; converted to CSR).
        fmt: candidate format name.
        dia_max_diags: max distinct diagonals before DIA is skipped.
        ell_max_width_factor: max ``max_row_nnz / mean_row_nnz`` before ELL
            is skipped.
        bsr_min_block_fill: min nnz / occupied 32-block area before BSR is
            skipped.
        dense_max_bytes: max f32 bytes of the densified matrix.

    Returns:
        A human-readable skip reason, or ``None`` when the format is fine.

    Example:
        >>> import scipy.sparse as sp
        >>> structural_skip(sp.eye(64, format="csr"), "dia") is None
        True
    """
    if fmt == "dense" and 4 * s.shape[0] * s.shape[1] > dense_max_bytes:
        return f"dense={4 * s.shape[0] * s.shape[1]}B>{dense_max_bytes}B"
    s = s.tocsr()
    if s.nnz and not s.data.all():
        # guard on *logical* nonzeros, exactly like the feature-level mirror
        # (select.infeasible) — explicit stored zeros must not make the two
        # disagree, or prune could drop a candidate the race would keep
        s = s.copy()
        s.eliminate_zeros()
    if fmt == "dia":
        coo = s.tocoo()
        ndiags = len(np.unique(coo.col.astype(np.int64) - coo.row.astype(np.int64)))
        if ndiags > dia_max_diags:
            return f"ndiags={ndiags}>{dia_max_diags}"
    if fmt == "ell":
        counts = np.diff(s.indptr)
        mean_w = max(1.0, counts.mean() if len(counts) else 1.0)
        if len(counts) and counts.max() > ell_max_width_factor * mean_w + 8:
            return f"max_row={counts.max()} >> mean={mean_w:.1f}"
    if fmt == "bsr" and s.nnz:
        from .features import BSR_FEATURE_BLOCK, block_density

        coo = s.tocoo()
        fill = block_density(coo.row, coo.col, s.shape[0], s.shape[1],
                             BSR_FEATURE_BLOCK)
        if fill < bsr_min_block_fill:
            return f"block_fill={fill:.3f}<{bsr_min_block_fill}"
    return None


def autotune_spmv(
    a_dense,
    candidates: Optional[Sequence] = None,
    iters: int = 10,
    warmup: int = 3,
    dia_max_diags: int = 512,
    ell_max_width_factor: float = 4.0,
    dtype=None,
    policy: Optional[ExecutionPolicy] = None,
    prune: Optional[int] = None,
    time_fn=None,
) -> TuneResult:
    """Pick the fastest (format, backend) for ``a_dense`` on this backend.

    ``a_dense`` may be dense, scipy sparse, a registered container, or a
    ``SparseOperator``. Candidates are ``DispatchKey``s (legacy ``(fmt, impl)``
    tuples still accepted). Structural guards mirror Morpheus's practical
    limits: DIA is not built when the matrix has too many distinct diagonals
    (memory blow-up — the paper's FPGA section calls out exactly this), ELL
    when max row width far exceeds the mean (power-law matrices).

    Each candidate races under a *strict* policy (its backend alone, no
    fallback), so a timing always belongs to the kernel named on it. A
    backend whose capability predicate refuses the built container is
    skipped with reason ``"unsupported"``; a candidate that raises anything
    else fails the tune — a broken kernel is an error, not a slow entry.

    ``prune=k`` races only the top-``k`` candidates of the zero-run
    selector's ranking (``core/select.py``) — run-first stays the oracle
    among what is raced, the model just skips building/measuring candidates
    it is confident are slow; pruned keys land in ``TuneResult.skipped``
    with reason ``"pruned by selector"``. ``time_fn`` overrides the timing
    primitive (signature ``time_fn(fn, A, x, key, iters=, warmup=) -> us``)
    — tests inject a deterministic cost table through it.
    """
    import scipy.sparse as sp

    with obs.span("tune.race"):
        if isinstance(a_dense, SparseOperator):
            a_dense = a_dense.container
        if hasattr(a_dense, "to_dense") and not sp.issparse(a_dense):
            a_dense = _container_to_scipy(a_dense)
        s = a_dense if sp.issparse(a_dense) else sp.csr_matrix(np.asarray(a_dense))
        s = s.tocsr()
        n = s.shape[1]
        x = np.random.default_rng(0).standard_normal(n).astype(np.float32)
        x = jax.device_put(x)

        table: Dict[Tuple[str, str], float] = {}
        skipped: List[Tuple[str, str, str]] = []
        mats = {}
        skip_cache: Dict[str, Optional[str]] = {}  # structure stats once per fmt
        cand = _normalize_candidates(candidates if candidates is not None else DEFAULT_CANDIDATES)
        if prune:
            from . import select
            from .features import extract_features

            feats = extract_features(s)
            keep = {(k.format, k.backend) for k in select.prune_candidates(
                feats, int(prune),
                policy=policy if policy is not None else DEFAULT_POLICY,
                candidates=cand, dia_max_diags=dia_max_diags,
                ell_max_width_factor=ell_max_width_factor)}
            pruned_cand = []
            for fmt, impl in cand:
                # structurally infeasible keys stay in the loop so they are
                # skipped with their *structural* reason, not blamed on the
                # selector (the model only prunes feasible-but-predicted-slow)
                if (fmt, impl) in keep or select.infeasible(
                        feats, fmt, dia_max_diags, ell_max_width_factor) is not None:
                    pruned_cand.append((fmt, impl))
                else:
                    skipped.append((fmt, impl, "pruned by selector"))
            cand = tuple(pruned_cand)
        for fmt, impl in cand:
            if fmt not in skip_cache:
                skip_cache[fmt] = structural_skip(s, fmt, dia_max_diags,
                                                  ell_max_width_factor)
            why = skip_cache[fmt]
            if why is not None:
                skipped.append((fmt, impl, why))
                continue
            if impl not in available_impls(fmt):
                skipped.append((fmt, impl, "impl not registered"))
                continue
            with obs.span("tune.candidate", fmt=fmt, impl=impl):
                if fmt not in mats:
                    kw = {"dtype": dtype} if dtype is not None else {}
                    if fmt in _COL_TILED_FORMATS:
                        # candidates are measured under the caller's VMEM budget:
                        # large-n matrices get the matching column-tile plan built
                        # in, resident-under-this-policy ones skip it (or keep the
                        # single-tile SCS layout csr/sell always need)
                        base = policy if policy is not None else DEFAULT_POLICY
                        kw["col_tile"] = _col_tile_for_policy(fmt, n, base.col_tile(n))
                    mats[fmt] = _from_dense(s, fmt, **kw)
                A = mats[fmt]
                pol = (policy if policy is not None else DEFAULT_POLICY).replace(
                    backends=(impl,), allow_fallback=False)
                fn = jax.jit(lambda A, x, pol=pol: spmv(A, x, policy=pol))
                try:
                    if time_fn is not None:
                        table[(fmt, impl)] = time_fn(fn, A, x, DispatchKey(fmt, impl),
                                                     iters=iters, warmup=warmup)
                    else:
                        table[(fmt, impl)] = _time_call(fn, A, x, iters=iters, warmup=warmup)
                except BackendUnsupportedError:
                    skipped.append((fmt, impl, "unsupported"))

        if not table:
            raise RuntimeError("auto-tuner: no candidate succeeded")
        (fmt, impl), t = min(table.items(), key=lambda kv: kv[1])
        return TuneResult(fmt, impl, t, mats[fmt], table, skipped, base_policy=policy)


def optimal_format_distribution(suite, candidates=None, **kw) -> Dict[str, str]:
    """Fig. 3 / Fig. 7 analogue: winning format per matrix over a suite."""
    out = {}
    for name, mat in suite:
        res = autotune_spmv(mat, candidates=candidates, **kw)
        out[name] = f"{res.format}/{res.impl}"
    return out
