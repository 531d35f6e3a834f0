"""The layer reduction: each device op of a traced window goes to the
layer of the innermost `hkv.` scope of its instruction, joined by (module,
instruction) to the compiled text of the program that ran it.

The recorded case is one A.ingest.new batch (2^20 new keys into config A's
full 2^27-slot table) traced on a TPU v5e, with the compiled texts of the
window's three programs from the same run."""

import gzip
import json
import re
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from hkvbench import harness, layers, spec
from hkvbench.tests import tiny

DATA = Path(__file__).with_name("data")
XPLANE = DATA / "ingest_layers.xplane.pb.gz"
MODULES = DATA / "ingest_layers.modules.json.gz"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("layers") / "ingest.xplane.pb"
    with gzip.open(XPLANE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(MODULES, "rt") as f:
        texts = json.load(f)
    return path, texts


def test_recorded_window_by_layer(recorded):
    path, texts = recorded
    split = layers.reduce(path, texts)
    want = {"kernels": 3.121682833, "kernel_io": 0.003337872,
            "closure": 0.269580208, "op": 0.0, "client": 0.000024215}
    for layer, seconds in want.items():
        assert split.seconds[layer] == pytest.approx(seconds, abs=1e-9), layer
    # every op is on exactly one layer
    assert sum(split.seconds.values()) == pytest.approx(split.op_s, abs=1e-9)
    assert split.op_s <= split.busy_s + 1e-9
    assert split.busy_s == pytest.approx(3.394652703, abs=1e-9)
    assert split.mixed_s == pytest.approx(0.000144392, abs=1e-9)
    shares = split.shares()
    assert set(shares) == set(layers.SHARES)
    kernels = 100.0 * split.seconds["kernels"] / split.busy_s
    assert abs(kernels + sum(shares.values()) - 100.0) < 0.5


def test_recorded_window_needs_every_module(recorded):
    path, texts = recorded
    held = dict(texts)
    held.pop("jit_gen")
    with pytest.raises(layers.LayerError, match="no text"):
        layers.reduce(path, held)


def test_a_module_name_held_by_two_programs_is_an_error(recorded):
    _, texts = recorded
    insert = texts["jit_insert"]
    other = insert.replace("ENTRY", "ENTRY ", 1)
    with pytest.raises(layers.LayerError, match="two programs"):
        layers.module_texts([insert, other])
    assert layers.module_texts([insert, insert]) == {"jit_insert": insert}


def test_an_instruction_missing_from_its_text_is_an_error(recorded,
                                                          monkeypatch):
    path, texts = recorded
    monkeypatch.setattr(layers, "instr_seconds", lambda p: (
        {("jit_insert", "no_such_instruction.1"): 1.0}, 1.0))
    with pytest.raises(layers.LayerError, match="not in jit_insert"):
        layers.reduce(path, texts)


def test_scope_layer():
    assert layers.scope_layer(
        "jit(f)/hkv.op.find/hkv.kernel_io/while/body/add") == "kernel_io"
    assert layers.scope_layer(
        "jit(step)/hkv.emb.apply_grads/hkv.closure/sort") == "closure"
    assert layers.scope_layer(
        "jit(step)/hkv.emb.apply_grads/scatter-add") == "op"
    assert layers.scope_layer(
        "jit(f)/hkv.op.find_or_insert/hkv.closure/hkv.op.find/mul") == "op"
    assert layers.scope_layer("jit(gen)/add") == "client"
    assert layers.scope_layer("kl") is None           # a parameter's name
    assert layers.scope_layer(None) is None           # compiler-inserted
    with pytest.raises(layers.LayerError, match="unknown program scope"):
        layers.scope_layer("jit(f)/hkv.window/add")


def _scoped(x):
    with jax.named_scope("hkv.op.find"):
        y = x * 3
        with jax.named_scope("hkv.closure"):
            y = jnp.sort(y)
        with jax.named_scope("hkv.kernel_io"):
            y = jnp.cumsum(y[::-1])
    return y + 1


def test_cpu_compiled_text_by_layer():
    text = jax.jit(_scoped).lower(
        jnp.arange(4096, dtype=jnp.float32)).compile().as_text()
    name, by_instr = layers.parse_module(text)
    assert name == "jit__scoped"
    found = {layer for layer, _ in by_instr.values()}
    assert {"closure", "kernel_io", "client"} <= found
    assert "kernels" not in found            # no Pallas kernel on the CPU
    sorts = re.findall(r"%([\w.]+) = \S+ sort\(", text)
    assert sorts and all(by_instr[n][0] == "closure" for n in sorts)


def test_compiler_inserted_instruction_takes_its_consumers_layer():
    text = """HloModule jit_m, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused (p.0: f32[8]) -> f32[8] {
  %p.0 = f32[8]{0} parameter(0)
  %a.1 = f32[8]{0} add(%p.0, %p.0), metadata={op_name="jit(m)/hkv.op.find/hkv.kernel_io/add"}
  ROOT %m.1 = f32[8]{0} multiply(%a.1, %a.1), metadata={op_name="jit(m)/mul"}
}

ENTRY %main (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %copy.1 = f32[8]{0} copy(%x.1)
  %s.1 = f32[8]{0} sort(%copy.1), dimensions={0}, metadata={op_name="jit(m)/hkv.op.find/hkv.closure/sort"}
  ROOT %fusion.1 = f32[8]{0} fusion(%s.1), kind=kLoop, calls=%fused, metadata={op_name="jit(m)/mul"}
}
"""
    name, by_instr = layers.parse_module(text)
    assert name == "jit_m"
    assert by_instr["copy.1"] == ("closure", False)   # feeds the sort
    assert by_instr["s.1"] == ("closure", False)
    # a fusion counts for its root's op_name and is marked as spanning two
    assert by_instr["fusion.1"] == ("client", True)


@pytest.mark.parametrize("name", tiny.cells())
def test_programs_of_every_entry_compile_to_distinct_modules(name):
    cell = tiny.cell(name)
    run = harness.Run(cell, 2**31 + 11, 0.1, None, jax.devices()[:1])
    entry = cell.traffic["entry"]
    ent = spec.entry(entry)
    st = ent.setup(run)
    ent.window(run, st)
    progs = ent.programs(run, st)
    texts = layers.module_texts(layers.compiled_texts(progs))
    assert len(texts) == len(progs)
    assert "jit_convert_element_type" in texts
    for text in texts.values():
        layers.parse_module(text)
