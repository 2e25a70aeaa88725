"""Structured SpMV/SpMM dispatch + the 'Plain' (pure-jnp) implementations.

Morpheus dispatches one implementation per (algorithm, backend) at compile
time; here the dispatch table is keyed by ``DispatchKey(format, backend)`` and
the jit cache plays the role of the compile-time dispatch. Backend names
mirror the paper's versions:

  - ``plain``  : straightforward jnp transliterations of Algorithms 1-3
                 (what the compiler gives you)
  - ``dense``  : densify + XLA matmul (the vendor-library / ArmPL analogue)
  - ``pallas`` : hand-tiled TPU kernels (the SVE-intrinsics analogue),
                 registered lazily by ``repro.kernels.ops``

Each registration may carry a declarative ``supports(A, policy)`` capability
predicate (the device-fit guards that used to live inside ``kernels/ops.py``);
dispatch walks the policy's backend chain and falls back to the next backend
when a predicate rejects. ``spmv(A, x, impl=...)`` / ``spmm(A, X, impl=...)``
remain as thin back-compat shims over the policy path and return bit-identical
results to the old string-dispatch API.

Dispatch is also the resilience lane's enforcement point (docs/resilience.md):
every kernel outcome feeds the ambient ``repro.core.health`` registry, a
quarantined ``DispatchKey`` is ordered behind its healthy chain peers, a
kernel that *raises* falls down the same chain (the failure is wrapped in
``KernelExecutionError`` only when the chain is exhausted), and under
``policy.check_finite`` a concrete non-finite result counts as a failure.
The ``fire``/``corrupt`` hooks of an active ``FaultPlan``
(``repro.resilience.faults``) are consulted at the same spots and are a
single ``None``-check when no plan is armed.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import health as _health
from . import obs as _obs
from .errors import BackendUnsupportedError, KernelExecutionError, _all_finite
from .formats import BSR, COO, CSR, DIA, ELL, SELL, Dense
from .operator import ExecutionPolicy, current_policy, policy_for_impl

# ------------------------------------------------------------- dispatch ----


@dataclass(frozen=True)
class DispatchKey:
    """One slot of the dispatch table: (container format, backend name)."""

    format: str
    backend: str

    def __iter__(self):  # allow `fmt, backend = key` unpacking
        return iter((self.format, self.backend))


@dataclass(frozen=True)
class KernelEntry:
    key: DispatchKey
    fn: Callable
    supports: Optional[Callable] = None  # (A, policy) -> bool; None = always
    needs_policy: bool = False  # fn takes the policy (multi-strategy kernels)

    def ok(self, A, policy: ExecutionPolicy) -> bool:
        return self.supports is None or bool(self.supports(A, policy))

    def call(self, A, *operands, policy: ExecutionPolicy):
        """Invoke the kernel; strategy-picking kernels (resident vs column-
        tiled) receive the policy as a trailing argument."""
        if self.needs_policy:
            return self.fn(A, *operands, policy)
        return self.fn(A, *operands)


_SPMV: Dict[DispatchKey, KernelEntry] = {}
_SPMM: Dict[DispatchKey, KernelEntry] = {}
_SPMV_MASKED: Dict[DispatchKey, KernelEntry] = {}


def register_spmv(fmt: str, backend: str, supports: Optional[Callable] = None,
                  needs_policy: bool = False):
    """Decorator registering an SpMV kernel under ``DispatchKey(fmt, backend)``.

    Args:
        fmt: container format name (``"coo"``, ``"csr"``, ...) — must match
            the container class's ``format`` tag.
        backend: backend name the policy chain selects (``"plain"``,
            ``"pallas"``, ``"dense"``, ...).
        supports: optional ``(A, policy) -> bool`` capability predicate (the
            declarative device-fit guard); ``None`` means always supported.
        needs_policy: when True the kernel is called ``fn(A, x, policy)`` so
            it can pick an execution strategy (resident vs column-tiled)
            from the policy's VMEM budget.

    Returns:
        The decorator; the wrapped ``fn(A, x) -> y`` is returned unchanged.

    Registering a kernel makes it reachable by every dispatch path (operator
    ``@``, the auto-tuner, the distributed format groups) **and** adds a
    cell to the conformance grid — see the gap policy in
    ``docs/architecture.md``: a previously-xfailed (fmt, backend) cell will
    XPASS and fail the suite until ``KNOWN_GAPS`` is updated.

    Example:
        >>> @register_spmv("coo", "demo-backend")
        ... def coo_spmv_demo(A, x):
        ...     return coo_spmv_plain(A, x)
        >>> "demo-backend" in available_impls("coo")
        True
        >>> _ = _SPMV.pop(DispatchKey("coo", "demo-backend"))  # tidy up
    """
    def deco(fn):
        key = DispatchKey(fmt, backend)
        _SPMV[key] = KernelEntry(key, fn, supports, needs_policy)
        return fn
    return deco


def register_spmm(fmt: str, backend: str, supports: Optional[Callable] = None,
                  needs_policy: bool = False):
    """Decorator registering a *native* SpMM kernel ``fn(A, X) -> Y``.

    Same key space and ``supports`` semantics as :func:`register_spmv`.
    Formats without a native SpMM fall back to the same backend's SpMV
    vmapped over columns, so registration is only worthwhile when a fused
    kernel beats that (e.g. BSR's MXU block matmul).
    """
    def deco(fn):
        key = DispatchKey(fmt, backend)
        _SPMM[key] = KernelEntry(key, fn, supports, needs_policy)
        return fn
    return deco


def register_masked_spmv(fmt: str, backend: str, supports: Optional[Callable] = None,
                         needs_policy: bool = False):
    """Decorator registering a row-masked SpMV kernel.

    Args:
        fmt / backend / supports: as :func:`register_spmv`.

    The wrapped ``fn(A, x, row_mask) -> y`` must return ``y == 0`` outside
    the mask, ideally predicating entries *before* the reduction (that is
    the point of a native masked kernel — one multicolor-SymGS color skips
    the other colors' work). Formats without one fall back to masking the
    plain product of the *same* backend, so masked callers retarget across
    formats/backends exactly like unmasked SpMV.
    """
    def deco(fn):
        key = DispatchKey(fmt, backend)
        _SPMV_MASKED[key] = KernelEntry(key, fn, supports, needs_policy)
        return fn
    return deco


def available_impls(fmt: str):
    """Backends with a registered SpMV kernel for ``fmt``.

    Example:
        >>> "plain" in available_impls("csr")
        True
    """
    _ensure_pallas()
    return tuple(sorted(k.backend for k in _SPMV if k.format == fmt))


def dispatch_table(op: str = "spmv") -> Dict[DispatchKey, KernelEntry]:
    """A snapshot of one dispatch table.

    Args:
        op: ``"spmv"`` | ``"spmm"`` | ``"masked_spmv"``.

    Returns:
        ``{DispatchKey: KernelEntry}`` copy (mutating it does not register
        kernels — use the ``register_*`` decorators).
    """
    _ensure_pallas()
    return dict({"spmv": _SPMV, "spmm": _SPMM, "masked_spmv": _SPMV_MASKED}[op])


_PALLAS_LOADED = False


def _ensure_pallas():
    global _PALLAS_LOADED
    if not _PALLAS_LOADED:
        from repro.kernels import ops  # noqa: F401  registers (fmt, "pallas")
        _PALLAS_LOADED = True


# BackendUnsupportedError is defined in .errors (the shared resilience
# taxonomy) and re-exported here for back-compat with every existing caller.


def _spmv_chain(A, policy: ExecutionPolicy) -> List[KernelEntry]:
    """Every registered + supporting entry along the policy's backend chain,
    healthy entries first (quarantined keys keep chain order *after* them —
    they still run when nothing healthy is left). With
    ``allow_fallback=False`` only the preferred backend is considered and a
    rejecting predicate raises instead of silently degrading."""
    if "pallas" in policy.backends:
        _ensure_pallas()
    tried: List[str] = []
    cands: List[KernelEntry] = []
    for backend in policy.backends:
        entry = _SPMV.get(DispatchKey(A.format, backend))
        if entry is not None and entry.ok(A, policy):
            if not policy.allow_fallback:
                return [entry]
            cands.append(entry)
            continue
        why = "unregistered" if entry is None else "unsupported"
        if not policy.allow_fallback:
            # fallback disabled: the preferred backend must run, whether it
            # is missing for this format or its predicate rejected
            raise BackendUnsupportedError(
                f"backend {backend!r} {why} for {A.format} matrix of shape "
                f"{tuple(A.shape)} under {policy} and fallback is disabled")
        tried.append(f"{backend}: {why}")
    if not cands:
        raise KeyError(
            f"no SpMV for format {A.format!r} under backend chain {policy.backends}; "
            f"tried [{'; '.join(tried)}]; registered: {sorted((k.format, k.backend) for k in _SPMV)}")
    return _health.registry().order(cands)


def select_spmv(A, policy: ExecutionPolicy) -> KernelEntry:
    """Walk the policy's backend chain; first registered + supporting entry
    wins, with quarantined keys (see ``repro.core.health``) deprioritised
    behind healthy ones. With ``allow_fallback=False`` a rejecting predicate
    raises instead of silently degrading (health is not consulted — strict
    mode means *this* backend or an error)."""
    return _spmv_chain(A, policy)[0]


def _run_chain(steps: List[Tuple[DispatchKey, Callable]],
               policy: ExecutionPolicy, opname: str, scope: str):
    """Execute the first step that completes; a step that raises (or returns
    non-finite output under ``check_finite``) records a failure against its
    key and control falls to the next step. The last step's failure is
    wrapped in ``KernelExecutionError`` — by then the chain is exhausted.
    Each step traces under the device scope ``<scope>/<format>/<backend>``,
    so the ops of the lane that ran carry its name."""
    reg = _health.registry()
    plan = _health._FAULT_PLAN
    last_exc: Optional[Exception] = None
    for i, (key, thunk) in enumerate(steps):
        final = (i == len(steps) - 1) or not policy.allow_fallback
        try:
            if plan is not None:
                plan.fire("kernel", key)
            with _obs.scope(scope, key.format, key.backend):
                y = thunk()
            if plan is not None:
                y = plan.corrupt("nonfinite", key, y)
        except Exception as e:
            reg.record_failure(key)
            if final:
                raise KernelExecutionError(
                    f"{opname} kernel {key.format}x{key.backend} failed with "
                    f"{type(e).__name__} and the chain {policy.backends} is "
                    f"exhausted") from e
            last_exc = e
            continue
        if policy.check_finite and not _all_finite(y):
            reg.record_nonfinite(key)
            err = KernelExecutionError(
                f"{opname} kernel {key.format}x{key.backend} produced "
                f"non-finite output (policy.check_finite)")
            if final:
                raise err
            last_exc = err
            continue
        reg.record_success(key)
        return y
    raise last_exc  # pragma: no cover — loop always returns or raises


def _dispatch_spmv(A, x, policy: ExecutionPolicy) -> jnp.ndarray:
    steps = [(e.key, (lambda e=e: e.call(A, x, policy=policy)))
             for e in _spmv_chain(A, policy)]
    return _run_chain(steps, policy, "SpMV", "spmv")


def _dispatch_spmm(A, X, policy: ExecutionPolicy) -> jnp.ndarray:
    """SpMM: native kernel when one is registered along the chain (BSR has a
    true MXU kernel — that is the point of the format), else vmapped SpMV.
    A native kernel that raises, is quarantined, or emits non-finite output
    degrades to the vmapped-SpMV lane (which walks its own health-aware
    chain)."""
    if "pallas" in policy.backends:
        _ensure_pallas()
    reg = _health.registry()
    plan = _health._FAULT_PLAN
    for backend in policy.backends:
        entry = _SPMM.get(DispatchKey(A.format, backend))
        if entry is None:
            if not policy.allow_fallback:
                # no native SpMM for the preferred backend: the vmapped-SpMV
                # path below still enforces strictness through select_spmv
                break
            continue
        if not entry.ok(A, policy):
            if not policy.allow_fallback:
                raise BackendUnsupportedError(
                    f"SpMM backend {backend!r} rejected {A.format} matrix of shape "
                    f"{tuple(A.shape)} under {policy} and fallback is disabled")
            continue
        if policy.allow_fallback and reg.blocked(entry.key):
            continue  # quarantined native kernel: next backend / vmapped lane
        try:
            if plan is not None:
                plan.fire("kernel", entry.key)
            with _obs.scope("spmm", entry.key.format, entry.key.backend):
                Y = entry.call(A, X, policy=policy)
            if plan is not None:
                Y = plan.corrupt("nonfinite", entry.key, Y)
        except Exception as e:
            reg.record_failure(entry.key)
            if not policy.allow_fallback:
                raise KernelExecutionError(
                    f"SpMM kernel {entry.key.format}x{entry.key.backend} failed "
                    f"with {type(e).__name__} and fallback is disabled") from e
            break  # degrade to the vmapped-SpMV lane
        if policy.check_finite and not _all_finite(Y):
            reg.record_nonfinite(entry.key)
            if not policy.allow_fallback:
                raise KernelExecutionError(
                    f"SpMM kernel {entry.key.format}x{entry.key.backend} produced "
                    f"non-finite output (policy.check_finite)")
            break
        reg.record_success(entry.key)
        return Y
    return jax.vmap(lambda col: _dispatch_spmv(A, col, policy),
                    in_axes=1, out_axes=1)(X)


def _dispatch_masked_spmv(A, x, row_mask, policy: ExecutionPolicy) -> jnp.ndarray:
    """y = mask ⊙ (A @ x): the color-sweep primitive of multicolor SymGS.

    Walks the policy's backend chain; a format with a native masked kernel
    (predicated early, skipping unmasked rows' work) wins, otherwise the
    *same backend's* unmasked kernel runs and the mask is applied after —
    so masked callers inherit every format/backend the dispatch table knows.
    Health and fault injection apply per (format, backend) key exactly as in
    unmasked dispatch (one breaker per key, masked and unmasked lanes share
    it: a broken kernel family is broken for both).
    """
    if "pallas" in policy.backends:
        _ensure_pallas()
    tried: List[str] = []
    steps: List[Tuple[DispatchKey, Callable]] = []
    for backend in policy.backends:
        key = DispatchKey(A.format, backend)
        entry = _SPMV_MASKED.get(key)
        if entry is not None and entry.ok(A, policy):
            steps.append((key, (lambda entry=entry:
                                entry.call(A, x, row_mask, policy=policy))))
            if not policy.allow_fallback:
                break
            continue
        base = _SPMV.get(key)
        if base is not None and base.ok(A, policy):
            steps.append((key, (lambda base=base:
                                jnp.where(row_mask,
                                          base.call(A, x, policy=policy), 0))))
            if not policy.allow_fallback:
                break
            continue
        why = "unregistered" if (entry is None and base is None) else "unsupported"
        if not policy.allow_fallback:
            raise BackendUnsupportedError(
                f"masked SpMV backend {backend!r} {why} for {A.format} matrix of "
                f"shape {tuple(A.shape)} under {policy} and fallback is disabled")
        tried.append(f"{backend}: {why}")
    if not steps:
        raise KeyError(
            f"no masked SpMV for format {A.format!r} under chain {policy.backends}; "
            f"tried [{'; '.join(tried)}]")
    steps = _health.registry().order(steps, key_of=lambda s: s[0])
    return _run_chain(steps, policy, "masked SpMV", "masked_spmv")


def masked_spmv(A, x: jnp.ndarray, row_mask: jnp.ndarray,
                impl: Optional[str] = None, *,
                policy: Optional[ExecutionPolicy] = None) -> jnp.ndarray:
    """Row-masked SpMV: ``where(row_mask, A @ x, 0)`` through the dispatch
    table. ``row_mask`` is a (nrows,) bool array; ``impl`` mirrors the legacy
    string spelling of ``spmv``."""
    A = _unwrap(A)
    return _dispatch_masked_spmv(A, x, row_mask, _shim_policy(A, impl, policy, _SPMV))


# ------------------------------------------------------ back-compat shims ----


def _unwrap(A):
    from .operator import SparseOperator

    return A.container if isinstance(A, SparseOperator) else A


def _shim_policy(A, impl: Optional[str], policy: Optional[ExecutionPolicy],
                 table: Dict[DispatchKey, KernelEntry]) -> ExecutionPolicy:
    if policy is not None:
        return policy
    if impl is None:
        return current_policy()
    # legacy strictness: an impl never registered for this format is an error,
    # while a registered-but-unsupported one silently falls back to plain
    # (that is exactly what the old in-kernel guards did).
    if impl == "pallas":
        _ensure_pallas()
    key = DispatchKey(A.format, impl)
    if key not in table and key not in _SPMV:
        raise KeyError(f"no kernel registered for {(A.format, impl)}; "
                       f"have {sorted((k.format, k.backend) for k in _SPMV)}")
    return policy_for_impl(impl)


def spmv(A, x: jnp.ndarray, impl: Optional[str] = None, *,
         policy: Optional[ExecutionPolicy] = None) -> jnp.ndarray:
    """Sparse matrix-vector product ``y = A @ x``.

    Args:
        A: a registered container or a ``SparseOperator`` (unwrapped).
        x: ``(ncols,)`` dense vector.
        impl: deprecated string spelling of the backend; prefer
            ``SparseOperator`` with an ``ExecutionPolicy`` (or the
            ``use_backend`` context manager).
        policy: explicit ``ExecutionPolicy`` (wins over ``impl``).

    Returns:
        ``(nrows,)`` dense result.

    Example:
        >>> import numpy as np
        >>> from repro.core import from_dense
        >>> A = from_dense(np.eye(3, dtype=np.float32) * 3, "csr")
        >>> [float(v) for v in spmv(A, np.ones(3, np.float32))]
        [3.0, 3.0, 3.0]
    """
    A = _unwrap(A)
    return _dispatch_spmv(A, x, _shim_policy(A, impl, policy, _SPMV))


def spmm(A, X: jnp.ndarray, impl: Optional[str] = None, *,
         policy: Optional[ExecutionPolicy] = None) -> jnp.ndarray:
    """Sparse @ dense-matrix product ``Y = A @ X`` (``X`` is ``(ncols, k)``).

    Uses a native SpMM kernel when one is registered along the policy's
    backend chain, else the same backend's SpMV vmapped over columns.
    ``impl`` is the deprecated string spelling, as in :func:`spmv`.
    """
    A = _unwrap(A)
    return _dispatch_spmm(A, X, _shim_policy(A, impl, policy, _SPMM))


# ---------------------------------------------------------------- plain ----

#: Contractions (dense, BSR blocks) run f32 in full: XLA's default on a TPU
#: is one bf16 pass, ~1e-3 relative error.
_FULL = jax.lax.Precision.HIGHEST


#: Mean stored entries per row from which ``coo/plain`` sums rows in place.
#: The in-place sum reads one value per row besides its passes over the
#: entries, the scatter-add makes one update per entry: on a TPU v5e at 2^20
#: rows the two cost about the same at one entry a row, and at two the
#: in-place sum takes less than half the scatter's time.
SEGMENTED_MIN_ROW_NNZ = 2

#: Lanes of one block of the segmented scan (one vreg row on a TPU).
_SCAN_BLOCK = 128


def _shift(a, d: int, fill):
    """``a`` moved ``d`` places along its last axis, ``fill`` shifted in."""
    pad = [(0, 0)] * (a.ndim - 1) + [(d, 0)]
    return jnp.pad(a[..., : a.shape[-1] - d], pad, constant_values=fill)


def _segmented_scan(key, val):
    """Inclusive sum of ``val`` over runs of equal ``key`` (sorted keys).

    Entry ``i`` ends up holding the sum of the entries ``j <= i`` of its own
    run; values of different runs are never added. Within blocks of
    ``_SCAN_BLOCK`` lanes, ``log2`` shifted adds, each masked where the key
    differs (sorted keys make an equal key at distance ``d`` mean the whole
    stretch between is that run). Each block's last partial sum is then
    scanned the same way, one level down, and carried into the block's
    first run where that run began in an earlier block.
    """
    n = val.shape[0]
    width = min(n, _SCAN_BLOCK)
    nb = -(-n // width)
    keys = jnp.pad(key, (0, nb * width - n), mode="edge").reshape(nb, width)
    vals = jnp.pad(val, (0, nb * width - n)).reshape(nb, width)
    d = 1
    while d < width:
        vals = vals + jnp.where(keys == _shift(keys, d, -1), _shift(vals, d, 0), 0)
        d *= 2
    if nb > 1:
        tails = _segmented_scan(keys[:, -1], vals[:, -1])
        carry = jnp.where(keys[1:, 0] == keys[:-1, -1], tails[:-1], 0)
        carry = jnp.concatenate([jnp.zeros((1,), carry.dtype), carry])
        vals = vals + jnp.where(keys == keys[:, :1], carry[:, None], 0)
    return vals.reshape(-1)[:n]


def _sorted_row_sums(row, prod, rowptr):
    """``y[r]`` = sum of ``prod`` over row ``r``'s entries, for row-sorted
    entries with row pointer ``rowptr``: the segmented scan's value at each
    row's last entry, 0 for an empty row. Pad sentinels past ``rowptr[-1]``
    are never read."""
    end = rowptr[1:]
    sums = _segmented_scan(row, prod)
    return jnp.where(end > rowptr[:-1], sums[jnp.maximum(end - 1, 0)], 0)


@register_spmv("coo", "plain")
def coo_spmv_plain(A: COO, x):
    """Algorithm 1: y[ai[i]] += av[i] * x[aj[i]].

    With a row pointer and at least ``SEGMENTED_MIN_ROW_NNZ`` entries a row
    on average, each row's products are summed in place over the sorted
    entries (:func:`_sorted_row_sums`, counted as ``coo_spmv.segmented_rows``
    per trace); otherwise they are scatter-added into y.
    """
    nrows = A.shape[0]
    prod = A.val * x[A.col]
    if A.rowptr is not None and prod.shape[0] >= SEGMENTED_MIN_ROW_NNZ * nrows:
        _obs.count("coo_spmv.segmented_rows", nrows)
        return _sorted_row_sums(A.row, prod, A.rowptr)
    y = jnp.zeros((nrows + 1,), prod.dtype)  # +1 bucket absorbs pad sentinels
    return y.at[A.row].add(prod)[:nrows]


@register_spmv("csr", "plain")
def csr_spmv_plain(A: CSR, x):
    """Algorithm 2 via indptr expansion (rowptr walk, vectorised)."""
    nrows = A.shape[0]
    prod = A.data * x[A.indices]
    y = jnp.zeros((nrows + 1,), prod.dtype)
    return y.at[A.row_ids()].add(prod)[:nrows]


def _dia_padded(x, nrows: int):
    """``x`` with ``nrows`` zeros on each side: every diagonal's window of
    ``nrows`` entries is then one contiguous slice."""
    z = jnp.zeros((nrows,), x.dtype)
    return jnp.concatenate([z, x, z])


def _dia_window(xp, offset, nrows: int):
    """``x[i + offset]`` for rows ``i`` (zero where it leaves x), read as one
    dynamic slice of the padded x: a gather of the same entries runs at
    ~10^8 entries/s on a TPU, the slice at memory bandwidth."""
    return jax.lax.dynamic_slice(xp, (offset + nrows,), (nrows,))


@register_spmv("dia", "plain")
def dia_spmv_plain(A: DIA, x):
    """Algorithm 3: inner loop over diagonals, rows vectorised (the paper's
    outer-loop vectorisation — contiguous loads of av along i, shifted dense
    loads of x, no horizontal reduction)."""
    nrows, ncols = A.shape
    i = jnp.arange(nrows, dtype=jnp.int32)
    x = jnp.asarray(x)
    xp = _dia_padded(x, nrows)

    def body(d, y):
        k = i + A.offsets[d]
        valid = (k >= 0) & (k < ncols)
        return y + jnp.where(valid, A.data[d] * _dia_window(xp, A.offsets[d], nrows), 0)

    # carry in the promoted product dtype, not the storage dtype: narrow
    # (bf16/f16) containers against f32 x accumulate in f32
    acc = jnp.promote_types(A.dtype, x.dtype)
    return jax.lax.fori_loop(0, A.ndiags, body, jnp.zeros((nrows,), acc))


@register_spmv("ell", "plain")
def ell_spmv_plain(A: ELL, x):
    valid = A.indices >= 0
    xk = x[jnp.where(valid, A.indices, 0)]
    return jnp.sum(jnp.where(valid, A.data * xk, 0), axis=1)


@register_spmv("sell", "plain")
def sell_spmv_plain(A: SELL, x):
    nrows = A.shape[0]
    rows = A.entry_rows()
    valid = A.indices >= 0
    prod = jnp.where(valid, A.data * x[jnp.where(valid, A.indices, 0)], 0)
    y = jnp.zeros((nrows + 1,), prod.dtype)
    return y.at[jnp.minimum(rows, nrows)].add(prod)[:nrows]


@register_spmv("bsr", "plain")
def bsr_spmv_plain(A: BSR, x):
    nrows, ncols = A.shape
    bs = A.bs
    nbcols = -(-ncols // bs)
    xp = jnp.zeros((nbcols * bs,), x.dtype).at[:ncols].set(x)
    xb = xp.reshape(nbcols, bs)
    valid = (A.bcols >= 0)[..., None]
    xg = jnp.where(valid, xb[jnp.where(A.bcols >= 0, A.bcols, 0)], 0)  # (nbr, w, bs)
    y = jnp.einsum("rwij,rwj->ri", A.blocks, xg, precision=_FULL).reshape(-1)
    return y[:nrows]


@register_spmv("dense", "plain")
@register_spmv("dense", "dense")
def dense_spmv(A: Dense, x):
    return jnp.matmul(A.data, x, precision=_FULL)


# ---------------------------------------------------------- masked plain ----
# Native row-masked kernels: the mask predicates entries *before* the reduce,
# the VPU analogue of running one multicolor-SymGS color as a masked sweep.

@register_masked_spmv("csr", "plain")
def csr_masked_spmv_plain(A: CSR, x, row_mask):
    nrows = A.shape[0]
    rows = A.row_ids()
    prod = jnp.where(row_mask[rows], A.data * x[A.indices], 0)
    y = jnp.zeros((nrows + 1,), prod.dtype)
    return y.at[rows].add(prod)[:nrows]


@register_masked_spmv("coo", "plain")
def coo_masked_spmv_plain(A: COO, x, row_mask):
    nrows = A.shape[0]
    keep = row_mask[jnp.minimum(A.row, nrows - 1)] & (A.row < nrows)
    prod = jnp.where(keep, A.val * x[A.col], 0)
    y = jnp.zeros((nrows + 1,), prod.dtype)
    return y.at[A.row].add(prod)[:nrows]


@register_masked_spmv("ell", "plain")
def ell_masked_spmv_plain(A: ELL, x, row_mask):
    valid = (A.indices >= 0) & row_mask[:, None]
    xk = x[jnp.where(A.indices >= 0, A.indices, 0)]
    return jnp.sum(jnp.where(valid, A.data * xk, 0), axis=1)


@register_masked_spmv("dia", "plain")
def dia_masked_spmv_plain(A: DIA, x, row_mask):
    nrows, ncols = A.shape
    i = jnp.arange(nrows, dtype=jnp.int32)
    x = jnp.asarray(x)
    xp = _dia_padded(x, nrows)

    def body(d, y):
        k = i + A.offsets[d]
        valid = (k >= 0) & (k < ncols) & row_mask
        return y + jnp.where(valid, A.data[d] * _dia_window(xp, A.offsets[d], nrows), 0)

    # carry in the promoted product dtype, not the storage dtype: narrow
    # (bf16/f16) containers against f32 x accumulate in f32
    acc = jnp.promote_types(A.dtype, x.dtype)
    return jax.lax.fori_loop(0, A.ndiags, body, jnp.zeros((nrows,), acc))


@register_masked_spmv("bsr", "plain")
def bsr_masked_spmv_plain(A: BSR, x, row_mask):
    # block-granular predication: zero masked rows inside each block before
    # the gather-einsum, so the unmasked reference path runs unchanged
    nbrows, bs = A.bcols.shape[0], A.bs
    m = jnp.zeros((nbrows * bs,), jnp.bool_).at[: A.shape[0]].set(row_mask)
    blocks = A.blocks * m.reshape(nbrows, 1, bs, 1).astype(A.blocks.dtype)
    return bsr_spmv_plain(BSR(A.bcols, blocks, A.shape), x)


# ------------------------------------------------------- dense fallback ----

def _via_dense(A, x):
    return jnp.matmul(A.to_dense(), x, precision=_FULL)


for _fmt in ("coo", "csr", "dia", "ell", "sell", "bsr"):
    register_spmv(_fmt, "dense")(_via_dense)


# ------------------------------------------------------------------ SpMM ----

@register_spmm("bsr", "plain")
@register_spmm("bsr", "dense")
def _bsr_spmm_plain(A: BSR, X):
    nrows, ncols = A.shape
    bs, nf = A.bs, X.shape[1]
    nbcols = -(-ncols // bs)
    Xp = jnp.zeros((nbcols * bs, nf), X.dtype).at[:ncols].set(X)
    Xb = Xp.reshape(nbcols, bs, nf)
    valid = (A.bcols >= 0)[..., None, None]
    Xg = jnp.where(valid, Xb[jnp.where(A.bcols >= 0, A.bcols, 0)], 0)  # (nbr,w,bs,nf)
    Y = jnp.einsum("rwij,rwjf->rif", A.blocks, Xg, precision=_FULL).reshape(-1, nf)
    return Y[:nrows]
