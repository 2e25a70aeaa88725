"""Zero-run (format, backend) selection from structural features.

The run-first auto-tuner (``core/autotune.py``) is this repo's oracle: it
*measures* every candidate. This module is the decision procedure the paper's
Fig. 3 classification implies and related work builds explicitly (Chen et
al. select formats from structural features without execution; Stylianou &
Weiland's dynamic-sparse-matrix work needs exactly such a cheap predictor to
make runtime switching pay): map :class:`~repro.core.features.MatrixFeatures`
plus an :class:`~repro.core.operator.ExecutionPolicy` to a **ranked list of
DispatchKeys** without running a single kernel.

The model is a per-(format, backend, strategy) cost estimate

    est_us = a + b * krows + c * kentries + d * krows * kentries

(``krows = nrows/1000``, ``kentries = stored_entries/1000``; the bilinear
``d`` term captures interpreted-Pallas grids whose per-step cost grows with
both the row count and the streamed volume), where ``stored_entries`` is the
format's padded storage volume derived from
the features (DIA stores ``ndiags * nrows``, ELL ``nrows * rownnz_max``, ...)
and the strategy (Pallas resident vs column-tiled) follows the policy's VMEM
budget exactly like dispatch does. The coefficients are *calibrated* — fit
with non-negative least squares against this machine's measured autotune
tables by ``benchmarks/calibrate_select.py``, which regenerates the tables
below — so the ranking reflects how the backends actually behave on the
platform (on CPU, interpreted Pallas scales with row count; on TPU the model
falls back to an analytic bandwidth estimate). Structural *infeasibility*
mirrors ``autotune.structural_skip`` bit-for-bit, so a ranking never proposes
a candidate the tuner would refuse to build.

Consumers:
  - ``SparseOperator.tune(mode="predict")`` — retarget without executing,
  - ``autotune_spmv(prune=k)`` — race only the top-k predicted candidates,
  - ``benchmarks/run.py --corpus`` — predicted-vs-measured winner per matrix.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import tiling
from .features import MatrixFeatures, extract_features
from .operator import DEFAULT_POLICY, ExecutionPolicy
from .spmv import DispatchKey

#: Structural-guard thresholds — shared with ``autotune.structural_skip`` so
#: the zero-run feasibility test and the tuner's build guard cannot drift.
DIA_MAX_DIAGS = 512
ELL_MAX_WIDTH_FACTOR = 4.0
#: BSR is refused when the 32-edge block fill drops below this — below it
#: the zero-padded blocks blow storage past 1/BSR_MIN_BLOCK_FILL x the
#: logical nonzeros, and the block lane loses to CSR/SELL on pure volume.
BSR_MIN_BLOCK_FILL = 0.125
#: The dense candidate is refused when its f32 n x m array would pass this:
#: 256 MiB holds an 8192 x 8192 matrix, while HPCG's 104³ operator would
#: need ~5 TB.
DENSE_MAX_BYTES = 1 << 28

#: Calibrated cost tables: platform -> (fmt, backend, strategy) ->
#: (a_us, b_us_per_krow, c_us_per_kentry, d_us_per_krow_kentry) — the four
#: coefficients of ``est_us = a + b*krows + c*kentries + d*krows*kentries``.
#: ``strategy`` is ``""`` for non-Pallas backends and
#: ``"resident"``/``"tiled"`` (or BSR's ``"block"`` grid) for Pallas, chosen
#: per call from the policy's VMEM budget (the same decision dispatch makes).
#: The ``"cpu"`` table is fit by ``benchmarks/calibrate_select.py`` from
#: measured autotune tables on the reference CPU runner (Pallas interprets,
#: so its cost scales with row count and column-tiled grids are punitive);
#: regenerate it after kernel-strategy changes. The ``"tpu"`` table is the
#: analytic bandwidth model (~900 GB/s HBM, per-entry bytes by format,
#: Pallas ≈ streamed, plain ≈ gather/scatter-penalised) — uncalibrated until
#: a TPU runner records real tables. Platforms with no table of their own
#: (gpu, future accelerators) use the analytic table too: they compile
#: Pallas natively, so the CPU table's interpreted-Pallas coefficients would
#: misrank them.
CostTable = Dict[Tuple[str, str, str], Tuple[float, float, float, float]]

COST: Dict[str, CostTable] = {
    # fit by `python -m benchmarks.calibrate_select` (NNLS over measured
    # autotune tables: small suite under the default + a 48-col tiny-cap
    # policy, banded/random at 512/1024/4096 under a 1024-col cap, so both
    # Pallas strategies anchor the fit at both ends); coverage of the
    # measured winner at fit time: top-2 93%, top-4 100% (top-1 is noise-
    # limited on this host — near-tied cells flip run to run)
    "cpu": {
        ("coo", "pallas", "resident"): (53.223, 371.154, 0.0, 347.27),
        ("coo", "pallas", "tiled"): (232.349, 8706.024, 0.0, 96.14),
        ("coo", "plain", ""): (0.0, 192.954, 50.758, 0.0),
        ("csr", "pallas", "resident"): (120.823, 169.644, 15.784, 37.248),
        ("csr", "pallas", "tiled"): (65.959, 930.806, 0.0, 135.13),
        ("csr", "plain", ""): (96.052, 68.206, 55.797, 6.725),
        ("dense", "dense", ""): (22.084, 31.091, 0.25, 0.0),
        ("dia", "pallas", "resident"): (10.513, 0.0, 0.118, 3.832),
        ("dia", "pallas", "tiled"): (226.402, 0.0, 16.959, 0.0),
        ("dia", "plain", ""): (2.888, 80.675, 2.808, 0.0),
        ("ell", "pallas", "resident"): (40.064, 0.0, 0.421, 8.196),
        ("ell", "pallas", "tiled"): (27.837, 730.713, 0.0, 110.608),
        ("ell", "plain", ""): (46.548, 0.0, 2.248, 0.11),
        ("sell", "pallas", "resident"): (114.122, 85.527, 25.383, 24.511),
        ("sell", "pallas", "tiled"): (30.455, 1565.35, 0.0, 108.465),
        ("sell", "plain", ""): (85.504, 0.0, 53.976, 2.465),
        # bsr rows are hand-fit against block_random timings on the same
        # reference runner (calibrate_select's suite has no block matrices
        # yet): plain is a batched einsum over resident blocks, interpreted
        # Pallas pays the usual per-grid-step row tax
        ("bsr", "plain", ""): (60.0, 0.0, 1.2, 0.05),
        ("bsr", "pallas", "block"): (90.0, 420.0, 0.0, 55.0),
    },
    "tpu": {
        ("coo", "plain", ""): (10.0, 0.0, 0.045, 0.0),
        ("csr", "plain", ""): (10.0, 0.0, 0.035, 0.0),
        ("dia", "plain", ""): (10.0, 0.0, 0.01, 0.0),
        ("ell", "plain", ""): (10.0, 0.0, 0.02, 0.0),
        ("sell", "plain", ""): (10.0, 0.0, 0.025, 0.0),
        ("dense", "dense", ""): (10.0, 0.0, 0.009, 0.0),
        ("coo", "pallas", "resident"): (8.0, 0.0, 0.014, 0.0),
        ("csr", "pallas", "resident"): (8.0, 0.0, 0.010, 0.0),
        ("dia", "pallas", "resident"): (8.0, 0.0, 0.005, 0.0),
        ("ell", "pallas", "resident"): (8.0, 0.0, 0.010, 0.0),
        ("sell", "pallas", "resident"): (8.0, 0.0, 0.010, 0.0),
        ("coo", "pallas", "tiled"): (12.0, 0.0, 0.018, 0.0),
        ("csr", "pallas", "tiled"): (12.0, 0.0, 0.013, 0.0),
        ("dia", "pallas", "tiled"): (12.0, 0.0, 0.007, 0.0),
        ("ell", "pallas", "tiled"): (12.0, 0.0, 0.013, 0.0),
        ("sell", "pallas", "tiled"): (12.0, 0.0, 0.013, 0.0),
        # storage_entries already prices BSR's zero-padding blow-up, so the
        # per-entry coefficient is near the streamed floor: dense MXU tiles,
        # one int32 id per 32x32 block
        ("bsr", "plain", ""): (10.0, 0.0, 0.02, 0.0),
        ("bsr", "pallas", "block"): (8.0, 0.0, 0.008, 0.0),
    },
}


@dataclass(frozen=True)
class Prediction:
    """One ranked candidate: the key, its cost estimate, and why."""

    key: DispatchKey
    est_us: float
    reason: str

    def __repr__(self):
        return (f"Prediction({self.key.format}/{self.key.backend}, "
                f"{self.est_us:.1f}us, {self.reason!r})")


def storage_entries(f: MatrixFeatures, fmt: str) -> float:
    """Stored scalar entries (padding included) of ``f`` in format ``fmt`` —
    the volume term of the cost model.

    Example:
        >>> import scipy.sparse as sp
        >>> from repro.core.features import extract_features
        >>> f = extract_features(sp.eye(16, format="csr"))
        >>> storage_entries(f, "csr"), storage_entries(f, "dia")
        (16.0, 16.0)
        >>> storage_entries(f, "dense")
        256.0
    """
    if fmt in ("coo", "csr"):
        return float(f.nnz)
    if fmt == "dia":
        return float(f.ndiags * f.nrows)
    if fmt == "ell":
        return float(f.nrows * max(f.rownnz_max, 1))
    if fmt == "sell":
        # slices pad to their own width; with σ-sorting the overhead is a
        # fraction of ELL's — estimate via the row-length spread
        spread = min(f.rownnz_std / max(f.rownnz_mean, 1.0), 1.0)
        return float(f.nnz) * (1.0 + 0.5 * spread) + float(f.nrows)
    if fmt == "dense":
        return float(f.nrows) * float(f.ncols)
    if fmt == "bsr":
        # nnz / fill at BSR's own 32-edge granularity = padded block volume
        return float(f.nnz) / max(f.block_density32, 1e-3)
    return float(f.nnz)


def plan_index_dtype(ncols: int, policy: ExecutionPolicy) -> np.dtype:
    """Index dtype a kernel plan built for an ``ncols``-wide matrix under
    ``policy`` would carry — the feature-level mirror of what
    ``tiling.local_index_dtype`` resolves at build time.

    Raises ``ValueError`` when the policy pins a dtype the tile width cannot
    hold (the same error the build would raise); :func:`rank` treats such a
    candidate as infeasible rather than proposing it.

    Example:
        >>> plan_index_dtype(96, DEFAULT_POLICY)
        dtype('int8')
    """
    ct = policy.col_tile(ncols) or max(1, ncols)
    return tiling.local_index_dtype(ct, policy.index_dtype)


def index_bytes(f: MatrixFeatures, fmt: str, policy: ExecutionPolicy,
                strategy: str) -> float:
    """Per-stored-entry *index* bytes the SpMV actually streams for this
    (format, strategy) under the policy's ``index_dtype`` knob.

    Plain/dense backends stream the container's int32 global indices; the
    column-tiled Pallas strategies (and the csr/sell SCS stream, whose
    resident mode is the single-tile case of the same plan) stream the
    plan's tile-local indices, compressed to the dtype the tile width
    allows. DIA streams offsets only (amortised to ~0 per entry); dense
    streams none.
    """
    if fmt in ("dia", "dense", "bsr"):
        return 0.0
    local = (fmt in ("csr", "sell")) or strategy == "tiled"
    ib = plan_index_dtype(f.ncols, policy).itemsize if local else 4
    if fmt == "coo":
        return 4.0 + ib  # int32 global rows ride along with every entry
    return float(ib)


def storage_bytes(f: MatrixFeatures, fmt: str,
                  policy: Optional[ExecutionPolicy] = None,
                  strategy: str = "") -> float:
    """Storage volume in bytes of ``f`` as ``fmt`` under the policy's
    precision knobs — ``storage_entries`` priced per entry: value bytes from
    ``value_dtype``, index bytes from :func:`index_bytes`, plus the
    per-row/per-diagonal metadata the format keeps (CSR's indptr, SELL's
    sptr+perm, DIA's offsets)."""
    policy = policy if policy is not None else DEFAULT_POLICY
    vb = policy.np_value_dtype().itemsize
    entries = storage_entries(f, fmt)
    per_entry = vb + index_bytes(f, fmt, policy, strategy)
    overhead = {"csr": 4.0 * (f.nrows + 1), "sell": 8.0 * f.nrows,
                "dia": 4.0 * f.ndiags}.get(fmt, 0.0)
    return entries * per_entry + overhead


def bytes_per_nnz(f: MatrixFeatures, fmt: str,
                  policy: Optional[ExecutionPolicy] = None,
                  strategy: str = "") -> float:
    """Streamed bytes per logical nonzero — the bandwidth-bound SpMV's
    dominant cost lever (Copernicus's compression-ratio axis).

    Example:
        >>> import scipy.sparse as sp
        >>> from repro.core.features import extract_features
        >>> f = extract_features(sp.eye(64, format="csr"))
        >>> b32 = bytes_per_nnz(f, "ell", DEFAULT_POLICY.replace(index_dtype="int32"))
        >>> bauto = bytes_per_nnz(f, "ell", DEFAULT_POLICY, strategy="tiled")
        >>> bauto < b32   # int8 local indices beat int32 global ones
        True
    """
    return storage_bytes(f, fmt, policy, strategy) / max(1, f.nnz)


def infeasible(f: MatrixFeatures, fmt: str,
               dia_max_diags: int = DIA_MAX_DIAGS,
               ell_max_width_factor: float = ELL_MAX_WIDTH_FACTOR,
               bsr_min_block_fill: float = BSR_MIN_BLOCK_FILL,
               dense_max_bytes: int = DENSE_MAX_BYTES,
               ) -> Optional[str]:
    """Feature-level mirror of ``autotune.structural_skip``: why ``fmt``
    should not even be built, or ``None``. Computed from features alone so
    the zero-run ranking refuses exactly what the run-first tuner refuses.

    Example:
        >>> import scipy.sparse as sp
        >>> from repro.core.features import extract_features
        >>> infeasible(extract_features(sp.eye(64, format="csr")), "dia")
    """
    if fmt == "dia" and f.ndiags > dia_max_diags:
        return f"ndiags={f.ndiags}>{dia_max_diags}"
    if fmt == "ell":
        mean_w = max(1.0, f.rownnz_mean)
        if f.rownnz_max > ell_max_width_factor * mean_w + 8:
            return f"max_row={f.rownnz_max} >> mean={mean_w:.1f}"
    if fmt == "bsr" and f.nnz and f.block_density32 < bsr_min_block_fill:
        return f"block_fill={f.block_density32:.3f}<{bsr_min_block_fill}"
    if fmt == "dense" and 4 * f.nrows * f.ncols > dense_max_bytes:
        return f"dense={4 * f.nrows * f.ncols}B>{dense_max_bytes}B"
    return None


#: the uncompressed pricing baseline of the analytic bandwidth scaling —
#: int32 indices, f32 values, whatever tile geometry the default budget gives
_UNCOMPRESSED = ExecutionPolicy(index_dtype="int32", value_dtype="float32")


def _platform() -> str:
    import jax

    return jax.default_backend()


def pallas_strategy_for(f: MatrixFeatures, policy: ExecutionPolicy,
                        fmt: str) -> str:
    """Which Pallas strategy the policy's VMEM budget implies for this
    matrix: the feature-level twin of ``kernels.ops.pallas_strategy`` (which
    needs the built container)."""
    if fmt == "dia":
        # the extent-tightened resident test (docs/formats.md)
        if tiling.dia_resident(f.ncols, f.band_extent, policy.resident_cols()):
            return "resident"
        return "tiled"
    if fmt == "coo":
        if f.nrows <= policy.max_onehot_rows and f.ncols <= policy.resident_cols():
            return "resident"
        return "tiled"
    if fmt == "bsr":
        # one strategy: the scalar-prefetched block grid — bwidth is already
        # the streaming loop, there is no column-tiled variant to pick
        return "block"
    return "resident" if policy.col_tile(f.ncols) is None else "tiled"


def estimate_us(f: MatrixFeatures, key: DispatchKey,
                policy: Optional[ExecutionPolicy] = None,
                platform: Optional[str] = None) -> float:
    """The model's time estimate for running SpMV as ``key`` on ``f``.

    On the analytic (bandwidth) tables the volume terms are scaled by the
    variant's bytes-per-entry ratio against the uncompressed int32+f32
    baseline — compressed indices / narrow values move fewer bytes, and a
    bandwidth-bound estimate should say so. The calibrated ``"cpu"`` table
    describes *interpreted* Pallas, whose run time does not track storage
    width, so it stays unscaled.
    """
    policy = policy if policy is not None else DEFAULT_POLICY
    platform = platform or _platform()
    # unknown platforms (gpu, new accelerators) compile Pallas natively, so
    # they take the analytic bandwidth table — the "cpu" table's coefficients
    # describe *interpreted* Pallas and would wrongly condemn every native
    # Pallas cell
    analytic = platform not in COST or platform == "tpu"
    table = COST[platform] if platform in COST else COST["tpu"]
    strategy = (pallas_strategy_for(f, policy, key.format)
                if key.backend == "pallas" else "")
    coef = table.get((key.format, key.backend, strategy))
    if coef is None:  # unmodelled cell the platform table never measured
        return float("inf")
    krows = f.nrows / 1e3
    kentries = storage_entries(f, key.format) / 1e3
    ratio = 1.0
    if analytic:
        base = storage_bytes(f, key.format, _UNCOMPRESSED, strategy)
        ratio = storage_bytes(f, key.format, policy, strategy) / max(base, 1.0)

    def _affine(c4):
        a, b, c, d = c4
        return a + (b * krows + (c * kentries + d * krows * kentries) * ratio)

    est = _affine(coef)
    if strategy == "tiled":
        # column tiling only adds overhead over the resident strategy on the
        # same matrix — floor the tiled estimate at the resident one so the
        # fit's extrapolation to tiny matrices cannot under-run it
        res = table.get((key.format, key.backend, "resident"))
        if res is not None:
            est = max(est, _affine(res))
    return est


def rank(a, policy: Optional[ExecutionPolicy] = None,
         candidates: Optional[Sequence] = None,
         platform: Optional[str] = None,
         dia_max_diags: int = DIA_MAX_DIAGS,
         ell_max_width_factor: float = ELL_MAX_WIDTH_FACTOR,
         ) -> List[Prediction]:
    """Rank candidate ``DispatchKey``s for ``a`` without executing anything.

    Args:
        a: a :class:`MatrixFeatures`, or anything ``extract_features``
            accepts (container, operator, scipy, dense).
        policy: execution policy whose VMEM budget picks the Pallas strategy
            (default: ``DEFAULT_POLICY``).
        candidates: keys to rank (default ``autotune.DEFAULT_CANDIDATES``);
            structurally infeasible formats are dropped, exactly as
            ``structural_skip`` would drop them.
        platform: cost-table key (default: ``jax.default_backend()``).

    Returns:
        Feasible candidates as :class:`Prediction`s, fastest-estimate first.

    Example:
        >>> import scipy.sparse as sp
        >>> tri = sp.diags([[1.0]*256]*3, [-1, 0, 1], shape=(256, 256))
        >>> preds = rank(tri, platform="tpu")
        >>> preds[0].key.format
        'dia'
    """
    f = a if isinstance(a, MatrixFeatures) else extract_features(a)
    policy = policy if policy is not None else DEFAULT_POLICY
    if candidates is None:
        from .autotune import DEFAULT_CANDIDATES

        candidates = DEFAULT_CANDIDATES
    keys = [DispatchKey(fmt, impl) for fmt, impl in candidates]
    out: List[Prediction] = []
    for key in keys:
        why = infeasible(f, key.format, dia_max_diags, ell_max_width_factor)
        if why is not None:
            continue
        strategy = (pallas_strategy_for(f, policy, key.format)
                    if key.backend == "pallas" else "")
        if key.backend == "pallas" and key.format not in ("dia", "bsr", "dense"):
            try:  # a pinned index dtype the tile width cannot hold: the
                plan_index_dtype(f.ncols, policy)  # build would raise, so
            except ValueError:                     # never propose the cell
                continue
        est = estimate_us(f, key, policy, platform)
        reason = (f"{storage_entries(f, key.format):.0f} stored entries"
                  + (f", {strategy}" if strategy else "")
                  + f", {bytes_per_nnz(f, key.format, policy, strategy):.1f} B/nnz")
        out.append(Prediction(key, est, reason))
    out.sort(key=lambda p: (p.est_us, p.key.format, p.key.backend))
    return out


def predict(a, policy: Optional[ExecutionPolicy] = None,
            candidates: Optional[Sequence] = None,
            platform: Optional[str] = None,
            dia_max_diags: int = DIA_MAX_DIAGS,
            ell_max_width_factor: float = ELL_MAX_WIDTH_FACTOR) -> Prediction:
    """Top-1 of :func:`rank` — the zero-run analogue of ``autotune_spmv``
    (same structural-guard knobs, so the two modes stay switchable).

    Raises:
        RuntimeError: when every candidate is structurally infeasible.
    """
    preds = rank(a, policy=policy, candidates=candidates, platform=platform,
                 dia_max_diags=dia_max_diags,
                 ell_max_width_factor=ell_max_width_factor)
    if not preds:
        raise RuntimeError("format selector: no feasible candidate")
    return preds[0]


def prune_candidates(a, keep: int,
                     policy: Optional[ExecutionPolicy] = None,
                     candidates: Optional[Sequence] = None,
                     platform: Optional[str] = None,
                     dia_max_diags: int = DIA_MAX_DIAGS,
                     ell_max_width_factor: float = ELL_MAX_WIDTH_FACTOR,
                     ) -> List[DispatchKey]:
    """The top-``keep`` predicted candidates, for ``autotune_spmv(prune=k)``:
    the run-first race stays the oracle, it just skips candidates the model
    is confident about. Infeasible formats cost nothing to keep (the tuner
    skips them structurally), so pruning only drops *feasible but predicted
    slow* keys."""
    preds = rank(a, policy=policy, candidates=candidates, platform=platform,
                 dia_max_diags=dia_max_diags,
                 ell_max_width_factor=ell_max_width_factor)
    return [p.key for p in preds[:max(1, keep)]]


def selection_drifted(before: MatrixFeatures, after: MatrixFeatures,
                      policy: Optional[ExecutionPolicy] = None,
                      candidates: Optional[Sequence] = None,
                      platform: Optional[str] = None) -> bool:
    """Would the zero-run winner change between two feature snapshots?

    The ground-truth companion to the cheap drift score
    (:meth:`repro.core.dynamic.DeltaOverlay.drift`): the score says "the
    structure moved a lot", this says "moved enough that selection *would*
    pick a different (format, backend)". The dynamic benchmark gate uses it
    to annotate which mutation steps actually flip the decision.
    """
    a = predict(before, policy=policy, candidates=candidates,
                platform=platform)
    b = predict(after, policy=policy, candidates=candidates,
                platform=platform)
    return a.key != b.key


#: package-level spellings (``repro.core.rank_formats`` reads better than a
#: bare ``rank`` next to the solver / autotune exports)
rank_formats = rank
predict_format = predict
