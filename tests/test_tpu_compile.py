"""The main-path kernels compile for a TPU v5e chip at real widths.

Interpret mode runs a kernel body on the CPU and accepts what the chip's
compiler refuses: unaligned DMA slices, blocks that break the (8, 128)
tiling, reductions over unsigned integers, scalar-memory overflow.  These
tests compile — never run — each kernel for a described (not attached)
v5e chip, at paper config A's widths (2^27 slots, dim 8 + 1 aux column,
dual-bucket) and a 2^20-key batch, through the same `kernels/ops.py`
wrappers the table ops call, and check that the compiled program holds
the kernel (`tpu_custom_call`).  Widths 32 and 64 (configs B-D) compile
at a one-chip share of their tables.

The topology is described inside a fixture: only one process may load the
TPU compiler library at a time, and a test worker that loads it keeps it.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.hkv_dlrm import PAPER_CONFIGS
from repro.core import table as table_mod
from repro.core.predicates import SweepPredicate
from repro.core.u64 import U64
from repro.embedding.sparse_opt import SparseOptimizer
from repro.kernels import ops as kops

BATCH = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state(sharding, cfg, values_sharding=None):
    """HKVState of ShapeDtypeStructs for `cfg` on the described chip."""
    b, s = cfg.num_buckets, cfg.slots_per_bucket
    meta = lambda dt: _shape(sharding, (b, s), dt)
    scalar = _shape(sharding, (), jnp.uint32)
    return table_mod.HKVState(
        key_hi=meta(jnp.uint32), key_lo=meta(jnp.uint32),
        digests=meta(jnp.uint8),
        score_hi=meta(jnp.uint32), score_lo=meta(jnp.uint32),
        values=_shape(values_sharding or sharding, cfg.value_plane_shape,
                      cfg.value_dtype),
        clock_hi=scalar, clock_lo=scalar, epoch=scalar)


def _keys(sharding, n=BATCH):
    return (_shape(sharding, (n,), jnp.uint32),
            _shape(sharding, (n,), jnp.uint32))


def _compile(fn, *args, donate=()):
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    return compiled.as_text()


def _config_a():
    return PAPER_CONFIGS["A"].embedding().config()


def _share_config(dim):
    """Rows of dim + 1 (rowwise_adagrad) floats: config A's table at dim 8,
    a 2^24-slot share at widths 32 and 64 (their 2^27-slot tables exceed
    one chip)."""
    cap = PAPER_CONFIGS["A"].capacity if dim == 8 else 1 << 24
    return table_mod.HKVConfig(capacity=cap, dim=dim, aux_value_dim=1,
                               buckets_per_key=2)


@pytest.mark.parametrize("variant,dim", [
    pytest.param(v, d, id=v if d == 8 else f"{v}-{d}")
    for v in ("tlp", "pipeline") for d in (8, 32, 64)])
def test_find_scan_compiles(one_chip, variant, dim):
    """The fused find, its metadata and value rings at every value pitch."""
    cfg = _config_a() if dim == 8 else _share_config(dim)
    fn = lambda st, kh, kl: kops.find_fused_kernel(
        st, cfg, U64(kh, kl), variant=variant, interpret=False)
    assert "tpu_custom_call" in _compile(fn, _state(one_chip, cfg),
                                         *_keys(one_chip))


@pytest.mark.parametrize("variant", ["tlp", "pipeline"])
def test_digest_scan_compiles(one_chip, variant):
    cfg = _config_a()
    fn = lambda st, kh, kl: kops.locate_kernel(
        st, cfg, U64(kh, kl), variant=variant, interpret=False)
    assert "tpu_custom_call" in _compile(fn, _state(one_chip, cfg),
                                         *_keys(one_chip))


@pytest.mark.parametrize("variant", ["tlp", "pipeline"])
def test_update_scan_compiles(one_chip, variant):
    cfg = _config_a()
    opt = SparseOptimizer("rowwise_adagrad")
    fn = lambda st, kh, kl, g: kops.update_rows_kernel(
        st, cfg, U64(kh, kl), g, opt, variant=variant,
        interpret=False).state
    grads = _shape(one_chip, (BATCH, cfg.dim), jnp.float32)
    assert "tpu_custom_call" in _compile(
        fn, _state(one_chip, cfg), *_keys(one_chip), grads, donate=(0,))


def test_upsert_probe_and_claim_scan_compile(one_chip):
    """The whole kernel-backed insert_or_assign: upsert_probe (locate and
    target selection), claim_scan, and the gather/scatter row stages."""
    cfg = _config_a()
    def fn(st, kh, kl, v):
        r = kops.insert_and_evict_kernel(st, cfg, U64(kh, kl), v,
                                         interpret=False)
        return r.state, r.evicted.values
    vals = _shape(one_chip, (BATCH, cfg.total_value_dim), jnp.float32)
    hlo = _compile(fn, _state(one_chip, cfg), *_keys(one_chip), vals,
                   donate=(0,))
    for name in ("hkv_upsert_probe", "hkv_claim_scan", "hkv_gather_rows",
                 "hkv_scatter_rows"):
        assert name in hlo, name


@pytest.mark.parametrize("dim", [8, 32, 64])
def test_gather_and_scatter_rows_compile(one_chip, dim):
    cfg = _share_config(dim)
    rows = _shape(one_chip, (BATCH,), jnp.int32)
    mask = _shape(one_chip, (BATCH,), jnp.bool_)
    upd = _shape(one_chip, (BATCH, cfg.total_value_dim), jnp.float32)
    plane = _shape(one_chip, cfg.value_plane_shape, jnp.float32)
    gather = lambda v, r, m: kops._gather(v, cfg, r, m, False)
    scatter = lambda v, r, u, m: kops._scatter(v, cfg, r, u, m, False, False)
    assert "tpu_custom_call" in _compile(gather, plane, rows, mask)
    assert "tpu_custom_call" in _compile(scatter, plane, rows, upd, mask,
                                         donate=(0,))


def test_sweep_match_and_bucket_stats_compile(one_chip):
    cfg = _config_a()
    pred = SweepPredicate.score_below(1 << 40)
    sweep = lambda st: kops.sweep_mask_kernel(st, cfg, pred, interpret=False)
    stats = lambda st: kops.bucket_stats_kernel(st, interpret=False)
    assert "tpu_custom_call" in _compile(sweep, _state(one_chip, cfg))
    assert "tpu_custom_call" in _compile(stats, _state(one_chip, cfg))


def test_tier_crossings_compile_with_pinned_host_plane(one_chip):
    """hmem value tier: the plane stays in pinned_host memory; indices,
    updates and masks cross to it, only the touched rows come back."""
    host = one_chip.with_memory_kind("pinned_host")
    cfg = table_mod.HKVConfig(capacity=PAPER_CONFIGS["D"].capacity, dim=64,
                              aux_value_dim=1, value_tier="hmem")
    w = cfg.total_value_dim
    plane = _shape(host, cfg.value_plane_shape, jnp.float32)
    rows = _shape(one_chip, (1 << 16,), jnp.int32)
    upd = _shape(one_chip, (1 << 16, w), jnp.float32)
    keep = _shape(one_chip, (cfg.capacity,), jnp.bool_)
    # a described array does not carry its memory space into tracing (a
    # real pinned_host array does), so the plane is typed host-resident
    # here; for an array already in host memory this moves nothing
    on_host = lambda v: jax.device_put(v, jax.memory.Space.Host)
    gather = lambda v, r: table_mod.tier_gather("hmem", on_host(v), r, w)
    scatter = lambda v, r, u: table_mod.tier_scatter("hmem", on_host(v), r, u)
    mask = lambda v, k: table_mod.tier_mask_rows("hmem", on_host(v), k, w)
    compiled = jax.jit(gather).lower(plane, rows).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 30)
    for fn, args in ((scatter, (plane, rows, upd)), (mask, (plane, keep))):
        # a jit returning the plane declares where it lives, as
        # `table.jit_returning_table` does for the table ops' callers
        compiled = jax.jit(fn, donate_argnums=0,
                           out_shardings=host).lower(*args).compile()
        assert compiled.output_shardings.memory_kind == "pinned_host"
        mem = compiled.memory_analysis()
        # the plane is updated in place in host memory, never copied to HBM
        assert mem.host_alias_size_in_bytes >= cfg.capacity * 4 * 64
        assert mem.temp_size_in_bytes < (1 << 30)
