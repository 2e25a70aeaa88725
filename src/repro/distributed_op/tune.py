"""Per-partition run-first auto-tuning (paper §VII-D, Table III).

The paper's distributed HPCG runs the auto-tuner *on every process*: each
rank times the candidate formats on its own local and remote sub-matrices
and keeps its own winner (the SVE build lands on DIA-local + COO-remote).
Here each partition's blocks are tuned with the same single-device
``autotune_spmv`` machinery — the run-first measurement a rank would make —
and the winners are assembled into one ``DistributedOperator`` whose format
groups realise the heterogeneous per-rank choices under SPMD.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core.autotune import autotune_spmv
from repro.core.convert import _as_scipy
from repro.core.distributed import split_local_remote
from repro.core.operator import ExecutionPolicy
from repro.core.spmv import DispatchKey

from .operator import STACKABLE_FORMATS, DistributedOperator

#: Default distributed candidates: every stackable format on the plain
#: backend. Pallas candidates can be passed explicitly where the mesh's
#: devices support them — note that stacked group containers carry no
#: column-tile ``KernelPlan`` (``build_stacked`` disables them: per-part
#: plan shapes don't stack), so plan-requiring pallas kernels (csr/sell,
#: and any column-tiled mode) fall back down the group's policy chain at
#: execution even if they won the unstacked race; the resident dia/ell/coo
#: pallas kernels run as raced.
DISTRIBUTED_CANDIDATES: Tuple[DispatchKey, ...] = (
    DispatchKey("csr", "plain"),
    DispatchKey("dia", "plain"),
    DispatchKey("ell", "plain"),
    DispatchKey("coo", "plain"),
)

_EMPTY_CHOICE = DispatchKey("coo", "plain")  # cheapest container for nnz=0


def _stackable(candidates) -> Tuple[DispatchKey, ...]:
    keys = tuple(DispatchKey(f, b) for f, b in candidates)
    kept = tuple(k for k in keys if k.format in STACKABLE_FORMATS)
    if not kept:
        raise ValueError(f"no stackable candidate in {keys}; distributed "
                         f"containers must be one of {STACKABLE_FORMATS}")
    return kept


def tune_partitions(
    a,
    mesh: Mesh,
    axis: str = "data",
    candidates: Optional[Sequence] = None,
    mode: str = "auto",
    iters: int = 5,
    warmup: int = 2,
    policy: Optional[ExecutionPolicy] = None,
    dtype=jnp.float32,
) -> Tuple[DistributedOperator, Dict]:
    """Tune every partition's local and remote block independently.

    Args:
        a: the global matrix (anything ``as_operator`` accepts).
        mesh / axis: the 1-D device axis rows will be sharded over.
        candidates: ``DispatchKey``s (or ``(fmt, backend)`` pairs) to race;
            non-stackable formats (sell/bsr) are filtered out. Defaults to
            :data:`DISTRIBUTED_CANDIDATES`.
        mode: halo mode for the built operator (``"auto"``/``"halo"``/
            ``"allgather"``); the tuner always times the split blocks.
        iters / warmup: per-candidate timing repetitions.
        policy: base ``ExecutionPolicy`` limits the candidates run under.
        dtype: value dtype of the built containers.

    Returns:
        ``(op, table)`` — the retargeted :class:`DistributedOperator` whose
        per-rank choices are the tuning winners, and a table mapping
        ``(rank, "local"|"remote")`` to that block's ``{(fmt, backend): us}``
        timings (empty remote blocks are assigned ``coo/plain`` unraced).

    Example (any 1-device mesh)::

        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        op, table = tune_partitions(M.fdm27(4, 4, 4), mesh)
        y = op @ op.device_put(np.ones(64))
    """
    s = _as_scipy(a).tocsr()
    nparts = int(mesh.shape[axis])
    cand = _stackable(candidates if candidates is not None
                      else DISTRIBUTED_CANDIDATES)
    locals_, remotes, _ = split_local_remote(
        s, nparts, halo=None if mode == "allgather" else "auto")

    lkeys, rkeys, table = [], [], {}
    devices = mesh.devices.reshape(-1)  # shard p lives on the p-th device
    for p in range(nparts):
        # each rank measures on its own device, as a process would
        with jax.default_device(devices[p]):
            res = autotune_spmv(locals_[p], candidates=cand, iters=iters,
                                warmup=warmup, policy=policy, dtype=dtype)
            lkeys.append(res.key)
            table[(p, "local")] = res.table
            if remotes[p].nnz == 0:
                rkeys.append(_EMPTY_CHOICE)
                continue
            res = autotune_spmv(remotes[p], candidates=cand, iters=iters,
                                warmup=warmup, policy=policy, dtype=dtype)
            rkeys.append(res.key)
            table[(p, "remote")] = res.table

    op = DistributedOperator.build(s, mesh, axis, local=tuple(lkeys),
                                   remote=tuple(rkeys), mode=mode,
                                   policy=policy, dtype=dtype)
    return op, table
