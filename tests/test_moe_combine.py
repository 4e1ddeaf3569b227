"""The expert layer's combine kernel (``ops/pallas/moe_combine.py``, interpret
mode here) against XLA's ``.at[kept].add(..., mode="drop")``, bit for bit,
and the ``moe_combine`` event the layer leaves a trace and direction."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate import moe
from paddle_tpu.ops.pallas import moe_combine as mc
from paddle_tpu.profiler import trace


def routed_rows(case, tokens, rows, rng):
    """Each row's token, ``tokens`` for a row routed nowhere, as a pass of
    the layer's buffer holds them: sorted by held expert, each expert's
    tokens ascending, the routed rows first."""
    top_k, held = 4, 4
    if case == "every_slot_held":  # every token's 4 slots on the 4 held
        slots = np.repeat(np.arange(tokens), top_k)
        experts = np.tile(np.arange(held), tokens)
    else:
        experts = rng.integers(0, 2 * held, tokens * top_k)
        slots = np.repeat(np.arange(tokens), top_k)
        keep = experts < held
        slots, experts = slots[keep], experts[keep]
        if case == "tokens_left_out":  # every third token holds no row
            keep = slots % 3 != 0
            slots, experts = slots[keep], experts[keep]
    order = np.lexsort((slots, experts))
    kept = np.full(rows, tokens, np.int32)
    n = min(rows, order.size)
    kept[:n] = slots[order][:n]
    return jnp.asarray(kept), n


@pytest.mark.parametrize("carried", [False, True], ids=["first", "later"])
@pytest.mark.parametrize("case", ["routed", "tokens_left_out",
                                  "every_slot_held"])
@pytest.mark.parametrize("block_bytes", [8 << 20, 8 * 256 * 4],
                         ids=["one_block", "blocks_of_8"])
def test_combine_is_the_scatter_add_bit_for_bit(block_bytes, case, carried,
                                                monkeypatch):
    """Blocks of 8 tokens (rows of one group split over block boundaries)
    and one block; tokens that hold no row, tokens whose every slot is held;
    NaN in every row past the routed ones; the first pass (zeros) and a
    later one (a carried sum)."""
    monkeypatch.setattr(mc, "BLOCK_BYTES", block_bytes)
    tokens, lanes, rows = 64, 256, 200
    rng = np.random.default_rng(len(case) + carried)
    kept, routed = routed_rows(case, tokens, rows, rng)
    part = jnp.asarray(rng.standard_normal((rows, lanes)), jnp.float32)
    part = jnp.where((jnp.arange(rows) < routed)[:, None], part, jnp.nan)
    total = (jnp.asarray(rng.standard_normal((tokens, lanes)), jnp.float32)
             if carried else None)
    block, why = mc.plan(tokens, lanes)
    assert why is None and block == (8 if block_bytes < 2**20 else 64)
    got = moe._combine(part, kept, total, tokens, block)
    base = total if carried else jnp.zeros((tokens, lanes), jnp.float32)
    want = base.at[kept].add(part, mode="drop")
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("tokens,lanes,why", [
    (64, 96, "lanes_not_blocks_of_128"), (60, 128, "tokens_not_rows_of_8")])
def test_other_shapes_keep_the_scatter_add(tokens, lanes, why):
    """A shape the kernel does not take runs XLA's scatter-add, says why,
    and gives the same sums."""
    assert mc.plan(tokens, lanes) == (None, why)
    rng = np.random.default_rng(1)
    kept, routed = routed_rows("routed", tokens, 96, rng)
    part = jnp.asarray(rng.standard_normal((96, lanes)), jnp.float32)
    got = moe._combine(part, kept, None, tokens, None)
    want = jnp.zeros((tokens, lanes), jnp.float32).at[kept].add(
        part, mode="drop")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("lanes,path", [(128, "kernel"), (96, "xla")])
def test_one_event_a_trace_and_direction(lanes, path):
    """The layer's forward and its backward each leave one ``moe_combine``
    event a trace (however many passes the loop makes), with the path the
    shape took."""
    tokens, rows, held = 48, 64, 2
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((tokens, lanes)), jnp.float32)
    gu = jnp.asarray(0.1 * rng.standard_normal((held, lanes, 32)),
                     jnp.float32)
    down = jnp.asarray(0.1 * rng.standard_normal((held, 16, lanes)),
                       jnp.float32)
    tok = jnp.asarray(np.sort(rng.integers(0, tokens, 2 * rows)), jnp.int32)
    wgt = jnp.ones((2 * rows,), jnp.float32)
    offsets = jnp.asarray([0, 40, 100], jnp.int32)  # two passes

    def loss(x, gu, down):
        return moe.held_experts_apply(x, wgt, gu, down, tok, offsets,
                                      rows).sum()

    before = len(trace.events(kind="moe_combine"))
    jax.grad(loss, (0, 1, 2))(x, gu, down)
    events = trace.events(kind="moe_combine")[before:]
    assert [e.site for e in events] == ["forward", "backward"]
    for e in events:
        want = dict(path=path, rows=rows, tokens=tokens, lanes=lanes,
                    token_block=16 if path == "kernel" else 0)  # 3 blocks
        if path == "xla":
            want["why"] = "lanes_not_blocks_of_128"
        assert e.attrs == want
