"""Registry of Pallas kernel entry points for the contract checker.

Every registered spec knows how to TRACE its kernel (``jax.make_jaxpr``
over tiny placeholder planes — no execution, no compile) and where its
source lives for findings.  New kernels must be registered here: the
cleanliness test asserts the registry covers every ``pl.pallas_call`` in
``src/repro/kernels``, so an unregistered kernel is itself a finding.

Trace shapes are deliberately tiny (8 buckets x 8 slots): the contracts
checked (DMA pairing, memory spaces, masked stores) are shape-independent
structure, and small shapes keep ``python -m repro.analysis`` fast.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.extend
import jax.numpy as jnp

from repro.core.predicates import KINDS
from repro.core.table import value_plane_shape

B, S, V, N = 8, 8, 8, 8   # buckets, slots/bucket, value width, queries
Q_TILE = 4                # pipeline-variant tile (must divide N)


def _planes():
    u32 = lambda: jnp.zeros((B, S), jnp.uint32)
    return {
        "digests": jnp.zeros((B, S), jnp.uint8),
        "key_hi": u32(), "key_lo": u32(),
        "score_hi": u32(), "score_lo": u32(),
        "values": jnp.zeros(value_plane_shape(B * S, V), jnp.float32),
    }


def _queries():
    z32 = lambda: jnp.zeros((N,), jnp.uint32)
    return {
        "bucket1": jnp.zeros((N,), jnp.int32),
        "bucket2": jnp.zeros((N,), jnp.int32),
        "qdigest": z32(), "qkey_hi": z32(), "qkey_lo": z32(),
    }


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    name: str                 # registry id, e.g. "find_scan_tlp"
    path: str                 # repo-relative source file for findings
    build: Callable[[], jax.extend.core.ClosedJaxpr]

    def trace(self) -> jax.extend.core.ClosedJaxpr:
        return self.build()


def _trace(fn, *args, **kwargs):
    return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)


def _spec_digest(variant):
    from repro.kernels import digest_scan as m
    p, q = _planes(), _queries()
    return _trace(m.digest_scan, p["digests"], p["key_hi"], p["key_lo"],
                  q["bucket1"], q["qdigest"], q["qkey_hi"], q["qkey_lo"],
                  variant=variant, q_tile=Q_TILE, interpret=True)


def _spec_find(variant):
    from repro.kernels import find_scan as m
    p, q = _planes(), _queries()
    return _trace(m.find_scan, p["digests"], p["key_hi"], p["key_lo"],
                  p["score_hi"], p["score_lo"], p["values"],
                  q["bucket1"], q["bucket2"], q["qdigest"], q["qkey_hi"],
                  q["qkey_lo"], width=V, variant=variant, q_tile=Q_TILE,
                  use_digest=True, interpret=True)


def _spec_upsert_probe():
    from repro.kernels import upsert_scan as m
    p, q = _planes(), _queries()
    return _trace(m.upsert_probe, p["digests"], p["key_hi"], p["key_lo"],
                  p["score_hi"], p["score_lo"], q["bucket1"], q["bucket2"],
                  q["qdigest"], q["qkey_hi"], q["qkey_lo"], q_tile=Q_TILE,
                  use_digest=True, interpret=True)


def _spec_claim_scan():
    from repro.kernels import upsert_scan as m
    p, q = _planes(), _queries()
    return _trace(m.claim_scan, p["key_hi"], p["key_lo"], p["score_hi"],
                  p["score_lo"], q["bucket1"], jnp.zeros((N,), jnp.int32),
                  q_tile=Q_TILE, interpret=True)


def _spec_sweep(kind):
    from repro.kernels import sweep_scan as m
    p = _planes()
    op = jnp.zeros((), jnp.uint32)
    return _trace(m.sweep_match, p["key_hi"], p["key_lo"], p["score_hi"],
                  p["score_lo"], op, op, op, op, kind=kind, interpret=True)


def _spec_bucket_stats():
    from repro.kernels import score_scan as m
    p = _planes()
    return _trace(m.bucket_stats, p["key_hi"], p["key_lo"], p["score_hi"],
                  p["score_lo"], bucket_tile=B, interpret=True)


def _spec_update(variant):
    from repro.embedding.sparse_opt import SparseOptimizer
    from repro.kernels import update_scan as m
    p, q = _planes(), _queries()
    # rowwise_adagrad with dim = V-1: dim + 1 aux col == V, so the trace
    # exercises both the embedding and aux column paths of the in-kernel
    # optimizer apply against the standard V-wide placeholder plane
    opt = SparseOptimizer("rowwise_adagrad")
    dim = V - 1
    qvalid = jnp.ones((N,), jnp.int32)
    grads = jnp.zeros((N, dim), jnp.float32)
    return _trace(m.update_scan, p["digests"], p["key_hi"], p["key_lo"],
                  p["values"], q["bucket1"], q["bucket2"], q["qdigest"],
                  q["qkey_hi"], q["qkey_lo"], qvalid, grads, opt=opt,
                  dim=dim, width=V, variant=variant, q_tile=Q_TILE,
                  use_digest=True, interpret=True)


def _spec_gather():
    from repro.kernels import gather as m
    p = _planes()
    return _trace(m.gather_rows, p["values"], jnp.zeros((N,), jnp.int32),
                  jnp.zeros((N,), jnp.int32), width=V, q_tile=Q_TILE,
                  interpret=True)


def _spec_scatter(add):
    from repro.kernels import scatter as m
    p = _planes()
    return _trace(m.scatter_rows, p["values"], jnp.zeros((N,), jnp.int32),
                  jnp.zeros((N, V), jnp.float32), jnp.zeros((N,), jnp.int32),
                  add=add, q_tile=Q_TILE, interpret=True)


def kernel_specs() -> Sequence[KernelSpec]:
    specs = [
        KernelSpec("digest_scan_tlp", "src/repro/kernels/digest_scan.py",
                   lambda: _spec_digest("tlp")),
        KernelSpec("digest_scan_pipeline", "src/repro/kernels/digest_scan.py",
                   lambda: _spec_digest("pipeline")),
        KernelSpec("find_scan_tlp", "src/repro/kernels/find_scan.py",
                   lambda: _spec_find("tlp")),
        KernelSpec("find_scan_pipeline", "src/repro/kernels/find_scan.py",
                   lambda: _spec_find("pipeline")),
        KernelSpec("upsert_probe", "src/repro/kernels/upsert_scan.py",
                   _spec_upsert_probe),
        KernelSpec("claim_scan", "src/repro/kernels/upsert_scan.py",
                   _spec_claim_scan),
        KernelSpec("bucket_stats", "src/repro/kernels/score_scan.py",
                   _spec_bucket_stats),
        KernelSpec("update_scan_tlp", "src/repro/kernels/update_scan.py",
                   lambda: _spec_update("tlp")),
        KernelSpec("update_scan_pipeline", "src/repro/kernels/update_scan.py",
                   lambda: _spec_update("pipeline")),
        KernelSpec("gather_rows", "src/repro/kernels/gather.py", _spec_gather),
        KernelSpec("scatter_rows", "src/repro/kernels/scatter.py",
                   lambda: _spec_scatter(False)),
        KernelSpec("scatter_rows_add", "src/repro/kernels/scatter.py",
                   lambda: _spec_scatter(True)),
    ]
    for kind in KINDS:
        specs.append(KernelSpec(
            f"sweep_match[{kind}]", "src/repro/kernels/sweep_scan.py",
            lambda k=kind: _spec_sweep(k)))
    return specs


def unregistered_kernel_files() -> list:
    """Kernel source files that call pallas_call but have no spec.

    The contract checker can only enforce what it traces; a kernel file
    missing from the registry silently escapes every rule, so the checker
    reports such files as findings.
    """
    import pathlib

    registered = {spec.path for spec in kernel_specs()}
    kernels_dir = pathlib.Path(__file__).resolve().parents[1] / "kernels"
    missing = []
    for p in sorted(kernels_dir.glob("*.py")):
        rel = f"src/repro/kernels/{p.name}"
        if "pallas_call" in p.read_text() and rel not in registered:
            missing.append(rel)
    return missing
