"""Checker 1: kernel jaxpr contracts (DMA pairing, memory spaces, masked stores).

Every registered kernel entry point (``repro.analysis.registry``) is traced
with ``jax.make_jaxpr`` — tiny shapes, no execution — and the Pallas kernel
body jaxpr is walked to enforce three contracts from DESIGN.md:

  dma-unpaired      every ``dma_start`` must have a matching ``dma_wait`` on
                    the same semaphore (and index pattern), and vice versa —
                    the §4.3 pipeline kernels' double-buffer discipline.  A
                    started-but-never-awaited copy reads garbage on hardware
                    (interpret mode hides it, which is why this is a static
                    check).
  memory-space      refs declared in ANY/HBM space may ONLY be touched by
                    async copies (``dma_start``/``dma_wait``); a direct
                    ``get``/``swap`` on an HBM ref compiles in interpret
                    mode but is illegal on TPU.  Semaphore refs may only
                    feed DMA/semaphore primitives.
  unmasked-store    every store into a float-dtype output ref or an
                    input/output-aliased ref must trace back to a
                    ``select_n`` (a ``jnp.where``-family mask select) — the
                    PR 1 ``scatter_rows`` stale-write bug class: an
                    unconditional lane store clobbers EMPTY slots or
                    masked-out rows.

Plus the §3.6 tier seam (``check_hmem_seam``): with ``value_tier='hmem'``
the host-resident value plane must never appear as a ``pallas_call``
operand — only row-granular gathers (``tier_gather``) may cross the
host/device boundary.
"""

from __future__ import annotations

import re
from typing import Iterable, Optional

import jax
import jax.extend
import jax.numpy as jnp

from repro.analysis.findings import Finding
from repro.analysis import registry as registry_mod

Literal = jax.extend.core.Literal

CHECKER = "kernel-contracts"

# primitives that pass a value through unchanged (for provenance walks)
_PASS = {"convert_element_type", "broadcast_in_dim", "reshape", "squeeze",
         "expand_dims", "copy", "slice", "transpose", "rev", "dynamic_slice"}
# call-like primitives whose params embed exactly one ClosedJaxpr under "jaxpr"
_CALLS = {"jit", "pjit", "closed_call", "core_call", "custom_jvp_call",
          "custom_vjp_call", "remat", "checkpoint"}
_DMA = {"dma_start", "dma_wait"}
_SEM_OK = _DMA | {"semaphore_signal", "semaphore_wait", "get_barrier_semaphore"}


# ---------------------------------------------------------------------------
# jaxpr plumbing
# ---------------------------------------------------------------------------


def _subjaxprs(eqn):
    """All raw Jaxpr objects embedded in an eqn's params."""
    for v in eqn.params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for item in items:
            inner = getattr(item, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner           # ClosedJaxpr
            elif hasattr(item, "eqns"):
                yield item            # raw Jaxpr


def iter_pallas_calls(jaxpr):
    """Yield every pallas_call eqn reachable from a (Closed)Jaxpr."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        else:
            for sub in _subjaxprs(eqn):
                yield from iter_pallas_calls(sub)


def _space(var) -> Optional[str]:
    """Memory space of a ref var ('any', 'hbm', 'smem', 'vmem',
    'semaphore_mem', 'None' for blocked default), or None for non-ref
    values."""
    m = re.match(r"(?:Mem)?Ref(?:<([^>(]*)[^>]*>)?\{",
                 str(getattr(var, "aval", "")))
    if m is None:
        return None
    return m.group(1) or "None"


def _dtype(var):
    return getattr(getattr(var, "aval", None), "dtype", None)


def _kernel_name(eqn) -> str:
    info = eqn.params.get("name_and_src_info")
    return getattr(info, "name", None) or str(info) or "<pallas>"


def _body_and_layout(eqn):
    """(body jaxpr, guarded output arg positions, semaphore-legal set)."""
    body = eqn.params["jaxpr"]
    gm = eqn.params["grid_mapping"]
    n_idx = gm.num_index_operands
    n_in = gm.num_inputs
    n_out = gm.num_outputs
    out_base = n_idx + n_in
    guarded = set()
    for k in range(n_out):
        dt = _dtype(body.invars[out_base + k])
        if dt is not None and jnp.issubdtype(dt, jnp.floating):
            guarded.add(out_base + k)
    for _, out_idx in (eqn.params.get("input_output_aliases") or ()):
        guarded.add(out_base + out_idx)
    return body, guarded


# ---------------------------------------------------------------------------
# ref-origin walk: find DMA events + direct ref accesses across sub-jaxprs
# ---------------------------------------------------------------------------


def _map_inner_invars(eqn, inner, env):
    """Best-effort origin mapping from an eqn's operands to a sub-jaxpr's
    invars (pjit: 1:1; cond: invars[1:]; scan/while: positional over the
    const/carry prefix, where the refs live)."""
    name = eqn.primitive.name
    outer = list(eqn.invars)
    if name == "cond":
        outer = outer[1:]
    elif name == "while":
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        if inner is getattr(eqn.params.get("cond_jaxpr"), "jaxpr", None):
            outer = outer[:cn] + outer[cn + bn:]
        else:
            outer = outer[cn:]
    inner_env = {}
    for iv, ov in zip(inner.invars, outer):
        if not isinstance(ov, Literal):
            org = env.get(id(ov))
            if org is not None:
                inner_env[id(iv)] = org
    return inner_env


def _walk_refs(body, events):
    """Collect (prim_name, eqn, [origin-per-invar]) for ref-touching eqns.

    Origins are ('arg', i) for kernel invars, propagated through nested
    call/control-flow jaxprs; None for values produced inside the body.
    """
    env = {id(v): ("arg", i) for i, v in enumerate(body.invars)}

    def walk(jaxpr, env):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in _DMA or name in ("get", "swap", "addupdate",
                                        "masked_swap", "semaphore_signal",
                                        "semaphore_wait"):
                origins = [None if isinstance(v, Literal) else env.get(id(v))
                           for v in eqn.invars]
                events.append((name, eqn, origins))
            for sub in _subjaxprs(eqn):
                inner = getattr(sub, "jaxpr", sub)
                walk(inner, _map_inner_invars(eqn, inner, env))

    walk(body, env)
    return events


def _dma_signature(eqn, origins):
    """(sem origin, index pattern) identifying one DMA start/wait site.

    The semaphore ref is the invar in semaphore space; the pattern is the
    trailing operands after it — literal ints stay literal, data-dependent
    indices widen to '*' (matches anything)."""
    sem_pos = None
    for k, v in enumerate(eqn.invars):
        if _space(v) == "semaphore_mem":
            sem_pos = k
    if sem_pos is None:
        return None
    sem_origin = origins[sem_pos]
    pat = tuple(
        int(v.val) if isinstance(v, Literal) else "*"
        for v in eqn.invars[sem_pos + 1:]
    )
    return (sem_origin, pat)


def _patterns_unify(a, b):
    for x, y in zip(a, b):
        if x != "*" and y != "*" and x != y:
            return False
    return True


# ---------------------------------------------------------------------------
# per-kernel checks
# ---------------------------------------------------------------------------


def _check_dma_pairing(name, path, events):
    starts = [(eqn, _dma_signature(eqn, org)) for p, eqn, org in events
              if p == "dma_start"]
    waits = [(eqn, _dma_signature(eqn, org)) for p, eqn, org in events
             if p == "dma_wait"]
    out = []

    def unmatched(mine, others, rule, what, needs):
        for eqn, sig in mine:
            if sig is None:
                continue
            ok = any(o is not None and o[0] == sig[0]
                     and _patterns_unify(o[1], sig[1])
                     for _, o in others)
            if not ok:
                out.append(Finding(
                    CHECKER, rule, name,
                    f"{what} on semaphore {sig[0]} (index pattern "
                    f"{sig[1]}) has no matching {needs} anywhere in the "
                    f"kernel body — the async copy is never "
                    f"{'retired' if needs == 'dma_wait' else 'issued'}",
                    path=path))
    unmatched(starts, waits, "dma-unpaired", "dma_start", "dma_wait")
    unmatched(waits, starts, "dma-wait-unstarted", "dma_wait", "dma_start")
    return out


def _check_memory_spaces(name, path, body, events):
    out = []
    for prim, eqn, origins in events:
        for k, v in enumerate(eqn.invars):
            sp = _space(v)
            if sp in ("any", "hbm") and prim not in _DMA:
                out.append(Finding(
                    CHECKER, "memory-space", name,
                    f"direct {prim} on an ANY/HBM-space ref (arg "
                    f"{origins[k]}) — HBM planes may only move via "
                    f"dma_start/dma_wait (make_async_copy)",
                    path=path))
            if sp == "semaphore_mem" and prim not in _SEM_OK:
                out.append(Finding(
                    CHECKER, "memory-space", name,
                    f"{prim} on a DMA semaphore ref (arg {origins[k]}) — "
                    f"semaphores may only feed DMA/semaphore primitives",
                    path=path))
    return out


def _producers(jaxpr):
    d = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            d[id(ov)] = eqn
    return d


def _traces_to_select(var, jaxpr, bindings, depth=0):
    """True iff `var` provably flows from a select_n through pass-through
    prims, call boundaries (both directions), scan carries, and cond
    branches (all branches must be masked)."""
    if depth > 64:
        return False
    while True:
        if isinstance(var, Literal):
            return False
        prods = _producers(jaxpr)
        eqn = prods.get(id(var))
        if eqn is None:
            b = bindings.get(id(var))
            if b is None:
                return False          # scope invar (a raw ref/operand)
            jaxpr, var, bindings = b
            continue
        name = eqn.primitive.name
        if name == "select_n":
            return True
        if name in _PASS:
            var = eqn.invars[0]
            continue
        if name in _CALLS:
            inner = eqn.params["jaxpr"]
            inner = getattr(inner, "jaxpr", inner)
            k = [id(o) for o in eqn.outvars].index(id(var))
            inner_bind = dict(bindings)
            for iv, ov in zip(inner.invars, eqn.invars):
                inner_bind[id(iv)] = (jaxpr, ov, bindings)
            jaxpr, var, bindings = inner, inner.outvars[k], inner_bind
            continue
        if name == "scan":
            inner = eqn.params["jaxpr"]
            inner = getattr(inner, "jaxpr", inner)
            k = [id(o) for o in eqn.outvars].index(id(var))
            inner_bind = dict(bindings)
            for iv, ov in zip(inner.invars, eqn.invars):
                inner_bind[id(iv)] = (jaxpr, ov, bindings)
            jaxpr, var, bindings = inner, inner.outvars[k], inner_bind
            continue
        if name == "cond":
            k = [id(o) for o in eqn.outvars].index(id(var))
            branches = eqn.params["branches"]
            for br in branches:
                inner = getattr(br, "jaxpr", br)
                inner_bind = dict(bindings)
                for iv, ov in zip(inner.invars, eqn.invars[1:]):
                    inner_bind[id(iv)] = (jaxpr, ov, bindings)
                if not _traces_to_select(inner.outvars[k], inner, inner_bind,
                                         depth + 1):
                    return False
            return True
        return False


def _check_masked_stores(name, path, body, guarded):
    """Every swap into a guarded ref must store a select_n-derived value."""
    out = []

    def walk(jaxpr, env, bindings):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("swap", "masked_swap"):
                ref = eqn.invars[0]
                org = env.get(id(ref))
                if org is not None and org[0] == "arg" and org[1] in guarded:
                    val = eqn.invars[1]
                    if not _traces_to_select(val, jaxpr, bindings):
                        out.append(Finding(
                            CHECKER, "unmasked-store", name,
                            f"store into guarded ref arg{org[1]} (float "
                            f"output / aliased plane) does not derive from "
                            f"a select_n mask — unconditional lane stores "
                            f"clobber EMPTY slots or masked-out rows "
                            f"(the scatter_rows stale-write class)",
                            path=path))
            for sub in _subjaxprs(eqn):
                inner = getattr(sub, "jaxpr", sub)
                inner_env = _map_inner_invars(eqn, inner, env)
                inner_bind = dict(bindings)
                for iv, ov in zip(
                        inner.invars,
                        eqn.invars[1:] if eqn.primitive.name == "cond"
                        else eqn.invars):
                    if not isinstance(ov, Literal):
                        inner_bind[id(iv)] = (jaxpr, ov, bindings)
                walk(inner, inner_env, inner_bind)

    env = {id(v): ("arg", i) for i, v in enumerate(body.invars)}
    walk(body, env, {})
    return out


def check_traced_kernel(name, path, closed_jaxpr) -> list[Finding]:
    """All three jaxpr contracts over every pallas_call in a trace."""
    out = []
    calls = list(iter_pallas_calls(closed_jaxpr))
    if not calls:
        out.append(Finding(CHECKER, "no-pallas-call", name,
                           "registered kernel entry traced to zero "
                           "pallas_call eqns — registry builder is stale",
                           path=path))
    for eqn in calls:
        body, guarded = _body_and_layout(eqn)
        events = _walk_refs(body, [])
        kname = f"{name}:{_kernel_name(eqn)}"
        out += _check_dma_pairing(kname, path, events)
        out += _check_memory_spaces(kname, path, body, events)
        out += _check_masked_stores(kname, path, body, guarded)
    return out


def check_kernels(specs=None) -> list[Finding]:
    out = []
    for spec in (specs if specs is not None else registry_mod.kernel_specs()):
        try:
            cj = spec.trace()
        except Exception as e:  # a broken registry builder is itself fatal
            out.append(Finding(CHECKER, "trace-failed", spec.name,
                               f"tracing raised {type(e).__name__}: {e}",
                               path=spec.path))
            continue
        out += check_traced_kernel(spec.name, spec.path, cj)
    if specs is None:
        for rel in registry_mod.unregistered_kernel_files():
            out.append(Finding(
                CHECKER, "unregistered-kernel", rel,
                "file calls pallas_call but has no KernelSpec in "
                "analysis/registry.py — its kernels escape every contract "
                "rule; register a trace builder for it",
                path=rel))
    return out


# ---------------------------------------------------------------------------
# §3.6 tier seam: hmem value plane must never feed a pallas_call
# ---------------------------------------------------------------------------

# prims through which hmem-plane taint propagates (pure reshaping of the
# whole plane); a gather/take breaks taint by design — that IS tier_gather's
# row-granular crossing
_TAINT_PASS = _PASS | {"stop_gradient"}


def _taint_reaches_pallas(jaxpr, tainted, where):
    hits = []

    def walk(jaxpr, tainted):
        for eqn in jaxpr.eqns:
            tin = [v for v in eqn.invars
                   if not isinstance(v, Literal) and id(v) in tainted]
            if not tin:
                continue
            name = eqn.primitive.name
            if name == "pallas_call":
                hits.append(Finding(
                    CHECKER, "hmem-seam", where,
                    "the hmem (host-tier) value plane flows into a "
                    "pallas_call operand — §3.6 requires host values to "
                    "cross only via row-granular tier_gather/tier_scatter, "
                    "never as whole-plane kernel operands",
                    path="src/repro/kernels/ops.py"))
            elif name in _CALLS or name in ("scan", "while", "cond"):
                for sub in _subjaxprs(eqn):
                    inner = getattr(sub, "jaxpr", sub)
                    outer = (eqn.invars[1:] if name == "cond"
                             else eqn.invars)
                    inner_t = {id(iv) for iv, ov in zip(inner.invars, outer)
                               if not isinstance(ov, Literal)
                               and id(ov) in tainted}
                    walk(inner, inner_t)
            elif name in _TAINT_PASS:
                for ov in eqn.outvars:
                    tainted.add(id(ov))

    walk(jaxpr, set(tainted))
    return hits


def check_hmem_seam() -> list[Finding]:
    from repro.core import ops as ops_mod
    from repro.core import table as table_mod
    from repro.core.table import HKVConfig
    from repro.core.u64 import U64

    cfg = HKVConfig(capacity=64, dim=4, slots_per_bucket=8,
                    value_tier="hmem")
    state = table_mod.create(cfg)
    n = 4
    kh = jnp.zeros((n,), jnp.uint32)
    kl = jnp.zeros((n,), jnp.uint32)
    vals = jnp.zeros((n, 4), jnp.float32)

    cases = {
        "find[hmem,kernel]": lambda s, h, l, v: ops_mod.find(
            s, cfg, U64(h, l), backend="kernel").values,
        "insert_or_assign[hmem,kernel]": lambda s, h, l, v:
            ops_mod.insert_or_assign(s, cfg, U64(h, l), v,
                                     backend="kernel").state,
        "erase_if[hmem,kernel]": lambda s, h, l, v: ops_mod.erase_if(
            s, cfg, _always(), backend="kernel").state,
        "update_rows[hmem,kernel]": lambda s, h, l, v:
            ops_mod.update_rows(s, cfg, U64(h, l), v, _sgd(),
                                backend="kernel").state,
    }
    out = []
    for label, f in cases.items():
        cj = jax.make_jaxpr(f)(state, kh, kl, vals)
        leaves = jax.tree_util.tree_leaves(state)
        vidx = next(i for i, leaf in enumerate(leaves)
                    if leaf is state.values)
        tainted = {id(cj.jaxpr.invars[vidx])}
        out += _taint_reaches_pallas(cj.jaxpr, tainted, label)
    return out


def _always():
    from repro.core.predicates import SweepPredicate
    return SweepPredicate.always()


def _sgd():
    from repro.embedding.sparse_opt import SparseOptimizer
    return SparseOptimizer("sgd")
