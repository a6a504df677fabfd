"""The online softmax's update in its COLUMN form, frozen as
`ops/pallas/decode_attention.py::_online_softmax_update` stood up to PR 43:
the row statistics narrowed to a one-lane column (`m_ref[:, 0:1]`), `[R, 1]`
`m_new` / `alpha` broadcast over the scores and the accumulator, and
`broadcast_to` stores back to the `[R, 128]` scratch. The lane-replicated
form that replaced it (PR 44) does the same float32 operations on the same
values in the same order, so every kernel that shares the update — the
decode walks, the chunk walks, the training forward — must give the SAME
BITS with this copy swapped in. The oracle of `test_kernels.py`,
`test_paged_prefill.py` and `test_mla_attention.py`; it is not to be edited
with the kernels."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np

_MODULES = [importlib.import_module("deepspeed_tpu.ops.pallas." + name)
            for name in ("decode_attention", "prefill_attention",
                         "flash_attention")]


def column_form_update(s, v, in_dtype, acc_ref, m_ref, l_ref):
    m_prev = m_ref[:, 0:1]
    l_prev = l_ref[:, 0:1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(in_dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def assert_same_bits_as_the_column_form(monkeypatch, kernel, *args):
    """`kernel(*args)` as the tree has it against the same call with the
    frozen column form under every module's name for the update."""
    got = kernel(*args)
    traced = []

    def frozen(*update_args):
        traced.append(1)
        column_form_update(*update_args)

    with monkeypatch.context() as patch:
        for module in _MODULES:
            patch.setattr(module, "_online_softmax_update", frozen)
        want = kernel(*args)
    assert traced, "the kernel did not go through the shared update"
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype
        g = np.asarray(g, np.float32)
        np.testing.assert_array_equal(g, np.asarray(w, np.float32))
        assert np.isfinite(g).all()
    return got
