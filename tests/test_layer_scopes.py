"""The program's layers are named scopes in the compiled module.

Every `@roles` op runs under `hkv.op.<op>`, `HKVEmbedding`'s training and
serving methods under `hkv.emb.<method>`, the batch closure under
`hkv.closure` and the kernel wrappers under `hkv.kernel_io`; each
instruction of a compiled program carries them in its `op_name`, which is
how a device profile puts an op on a layer.  The scopes are metadata
only: the compiled program is otherwise the same without them.
"""

import contextlib
import inspect
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import HKVTable, U64, ops, roles
from repro.embedding import HKVEmbedding

N = 200


def _op_names(compiled_text: str) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', compiled_text)


def _innermost(names) -> set:
    out = set()
    for n in names:
        hkv = [p for p in n.split("/") if p.startswith("hkv.")]
        if hkv:
            out.add(hkv[-1])
    return out


def _keys(n=N):
    return U64(jnp.zeros((n,), jnp.uint32), jnp.arange(n, dtype=jnp.uint32))


def _find_text():
    t = HKVTable.create(capacity=8 * 128, dim=8, backend="kernel")

    def find(t, k):
        r = t.find(k)
        return r.values, r.found

    return jax.jit(find).lower(t, _keys()).compile().as_text()


def _insert_text():
    t = HKVTable.create(capacity=8 * 128, dim=8, backend="kernel")
    vals = jnp.ones((N, 8), jnp.float32)
    return jax.jit(lambda t, k, v: t.insert_or_assign(k, v).table).lower(
        t, _keys(), vals).compile().as_text()


def _train_text():
    emb = HKVEmbedding(capacity=8 * 128, dim=8, backend="kernel")
    t = emb.create()

    def step(t, tok):
        t, rows = emb.lookup_train(t, tok)
        return emb.apply_grads(t, tok, rows), rows

    tok = jnp.arange(N, dtype=jnp.int32) % 97
    return jax.jit(step).lower(t, tok).compile().as_text()


def test_find_carries_op_and_kernel_io_scopes():
    names = _op_names(_find_text())
    assert any("/hkv.op.find/" in n for n in names)
    assert {"hkv.kernel_io", "hkv.op.find"} <= _innermost(names)


def test_insert_carries_closure_and_kernel_io_scopes():
    names = _op_names(_insert_text())
    assert any("/hkv.op.insert_or_assign/hkv.closure/" in n for n in names)
    assert {"hkv.closure", "hkv.kernel_io"} <= _innermost(names)


def test_train_step_carries_emb_op_closure_and_kernel_io_scopes():
    names = _op_names(_train_text())
    inner = _innermost(names)
    assert {"hkv.emb.lookup_train", "hkv.emb.apply_grads", "hkv.closure",
            "hkv.kernel_io"} <= inner
    assert any("/hkv.emb.lookup_train/hkv.op.find_or_insert/" in n
               for n in names)
    assert any("/hkv.emb.apply_grads/hkv.op.update_rows/" in n
               for n in names)
    # the gradient dedupe is the closure's, inside the embedding method
    assert any("/hkv.emb.apply_grads/hkv.closure/" in n for n in names)


@pytest.mark.parametrize("text", [_find_text, _insert_text])
def test_scopes_are_metadata_only(monkeypatch, text):
    strip = lambda t: re.sub(r", metadata=\{[^}]*\}", "", t.split("\n\n%", 1)[1])
    scoped = text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = text()
    assert "hkv." in scoped and "hkv." not in plain
    assert strip(scoped) == strip(plain)


def test_role_of_sees_through_the_scope_wrapper():
    public = {name: fn for name, fn in vars(ops).items()
              if inspect.isfunction(fn) and not name.startswith("_")
              and fn.__module__ == ops.__name__
              and list(inspect.signature(fn).parameters)[:1] == ["state"]}
    annotated = {n: roles.role_of(fn) for n, fn in public.items()}
    decorated = inspect.getsource(ops).count("\n@roles.")
    assert len(annotated) == decorated and None not in annotated.values()
    assert annotated["find"] == roles.READER
    assert annotated["update_rows"] == roles.UPDATER
    assert annotated["insert_or_assign"] == roles.INSERTER
    # the wrapper keeps the op's name, signature and source
    assert ops.find.__name__ == "find"
    assert "telemetry" in inspect.signature(ops.find).parameters
    assert "def find(" in "".join(inspect.getsourcelines(ops.find)[0][:2])
