"""Inputs shared by the paged decode kernels' oracle tests (the float kernel
in test_serving.py, the int8 one in test_quant_serving.py), and the serving
tests' compile-count assertion."""
import jax.numpy as jnp
import numpy as np


def assert_one_compile_each(serving):
    """The serving promise after a trace that ran chunks and decode calls:
    `decode_step` and `prefill_step` compiled exactly once — and `mixed_step`
    once too, listed if and only if a chunk rode a decode call."""
    want = {"decode_step": 1, "prefill_step": 1}
    if serving.fused_chunks:
        want["mixed_step"] = 1
    assert serving.compile_stats() == want, serving.compile_stats()


def paged_kernel_case(heads, rows, seed=11):
    """Inputs of the paged decode kernels' oracle tests (the int8 twin in
    test_quant_serving.py uses them too): block 512, a 3-block table,
    shuffled physical blocks. `rows` "mixed": frontiers at 0, 511, 512, 513
    and the table's last position among dead rows (trash tables); "live":
    no dead row; "dead": nothing but dead rows. Returns (q, k, v, tables,
    pos, live)."""
    Hkv, G = heads
    rng = np.random.default_rng(seed)
    hd, bm, nb = 32, 512, 3
    pos = np.asarray([0, 511, 512, 513, nb * bm - 1, 700], np.int32)
    live = {"mixed": [True, True, True, True, True, False],
            "live": [True] * 6, "dead": [False] * 6}[rows]
    live = np.asarray(live)[rng.permutation(6)] if rows == "mixed" \
        else np.asarray(live)
    B, N = len(pos), 1 + nb * len(pos)
    tables = np.zeros((B, nb), np.int32)             # 0 = the trash block
    physical = iter(rng.permutation(np.arange(1, N)))
    for b in np.flatnonzero(live):
        for j in range(pos[b] // bm + 1):
            tables[b, j] = next(physical)
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(N, Hkv, bm, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(N, Hkv, bm, hd)), jnp.float32)
    return q, k, v, jnp.asarray(tables), jnp.asarray(pos), live


PAGED_KERNEL_HEADS = [(8, 4), (16, 1), (1, 8)]       # (Hkv, G)
PAGED_KERNEL_ROWS = ["mixed", "live", "dead"]
