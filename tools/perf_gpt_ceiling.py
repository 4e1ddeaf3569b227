"""GPT-345M ceiling study: hand-rolled pure-JAX transformer train step vs
the framework's compiled step.

The hand-rolled step uses raw jax/jnp + the same pallas flash-attention
kernel, bf16 weights with fp32 AdamW state, one donated jit — everything a
human JAX performance engineer would write, none of the framework. If the
framework step matches this, remaining headroom belongs to XLA/kernels,
not the framework.

Usage (on the TPU):  python tools/perf_gpt_ceiling.py [variant ...]
Variants: flash (default, lax.scan over layers), xla_attn, flash_bq512,
remat (jax.checkpoint per block), unrolled (python loop over layers — the
framework model's structure; XLA's own rematerialization applies)
"""
import functools
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.ops.pallas.flash_attention import flash_attention

VOCAB, HID, LAYERS, HEADS = 50304, 1024, 24, 16
SEQ = int(os.environ.get("BENCH_SEQ", 1024))
HD = HID // HEADS
FFN = 4 * HID
BSZ = int(os.environ.get("BENCH_BATCH", 8))
STEPS = int(os.environ.get("BENCH_STEPS", 10))
LR, WD, B1, B2, EPS = 1e-4, 0.01, 0.9, 0.999, 1e-8


def init_params(key):
    """bf16 weights (MXU-native), layout matching the framework model."""
    ks = jax.random.split(key, 8)
    init = lambda k, shape, s=0.02: (
        jax.random.normal(k, shape, jnp.float32) * s
    ).astype(jnp.bfloat16)
    L = LAYERS
    p = {
        "wte": init(ks[0], (VOCAB, HID)),
        "wpe": init(ks[1], (SEQ, HID)),
        "qkv_w": init(ks[2], (L, HID, 3 * HID)),
        "qkv_b": jnp.zeros((L, 3 * HID), jnp.bfloat16),
        "out_w": init(ks[3], (L, HID, HID), 0.02 / math.sqrt(2 * L)),
        "out_b": jnp.zeros((L, HID), jnp.bfloat16),
        "fc1_w": init(ks[4], (L, HID, FFN)),
        "fc1_b": jnp.zeros((L, FFN), jnp.bfloat16),
        "fc2_w": init(ks[5], (L, FFN, HID), 0.02 / math.sqrt(2 * L)),
        "fc2_b": jnp.zeros((L, HID), jnp.bfloat16),
        "ln1_g": jnp.ones((L, HID), jnp.float32),
        "ln1_b": jnp.zeros((L, HID), jnp.float32),
        "ln2_g": jnp.ones((L, HID), jnp.float32),
        "ln2_b": jnp.zeros((L, HID), jnp.float32),
        "lnf_g": jnp.ones((HID,), jnp.float32),
        "lnf_b": jnp.zeros((HID,), jnp.float32),
    }
    return p


def layer_norm(x, g, b):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + 1e-5) * g + b).astype(x.dtype)


def make_forward(attn_kind="flash", bq=None, bk=None, remat=False):
    scale = 1.0 / math.sqrt(HD)

    def attention(q, k, v):
        if attn_kind == "flash":
            kw = {}
            if bq:
                kw["block_q"] = bq
            if bk:
                kw["block_k"] = bk
            return flash_attention(q, k, v, scale=scale, causal=True, **kw)
        # xla_attn: dense softmax attention, XLA-fused
        qf = q.astype(jnp.float32) * scale
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
        mask = jnp.tril(jnp.ones((SEQ, SEQ), bool))
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    def block(h, lp):
        x = layer_norm(h, lp["ln1_g"], lp["ln1_b"])
        qkv = x @ lp["qkv_w"] + lp["qkv_b"]
        qkv = qkv.reshape(BSZ, SEQ, HEADS, 3, HD)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        a = attention(q, k, v).reshape(BSZ, SEQ, HID)
        h = h + a @ lp["out_w"] + lp["out_b"]
        x = layer_norm(h, lp["ln2_g"], lp["ln2_b"])
        m = jax.nn.gelu(x @ lp["fc1_w"] + lp["fc1_b"], approximate=True)
        h = h + m @ lp["fc2_w"] + lp["fc2_b"]
        return h

    if remat == "full":
        block = jax.checkpoint(block)
    elif remat == "dots":
        # save matmul outputs, recompute elementwise — the usual best
        # memory/flops trade for transformer blocks
        block = jax.checkpoint(
            block, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )

    stacked_keys = ("qkv_w", "qkv_b", "out_w", "out_b", "fc1_w", "fc1_b",
                    "fc2_w", "fc2_b", "ln1_g", "ln1_b", "ln2_g", "ln2_b")

    def forward(p, ids):
        h = p["wte"][ids] + p["wpe"][jnp.arange(SEQ)]

        def body(h, lp):
            return block(h, lp), None

        stacked = {k: p[k] for k in stacked_keys}
        h, _ = jax.lax.scan(body, h, stacked)
        h = layer_norm(h, p["lnf_g"], p["lnf_b"])
        return h.astype(jnp.float32) @ p["wte"].T.astype(jnp.float32)

    def forward_unrolled(p, ids):
        h = p["wte"][ids] + p["wpe"][jnp.arange(SEQ)]
        for i in range(LAYERS):
            lp = {k: p[k][i] for k in stacked_keys}
            h = block(h, lp)
        h = layer_norm(h, p["lnf_g"], p["lnf_b"])
        return h.astype(jnp.float32) @ p["wte"].T.astype(jnp.float32)

    return forward, forward_unrolled


def make_step(forward):
    def loss_fn(p, x, y):
        logits = forward(p, x)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)
        return nll.mean()

    def step(p, m, v, t, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        t = t + 1
        new_p, new_m, new_v = {}, {}, {}
        for k in p:
            gk = g[k].astype(jnp.float32)
            mk = B1 * m[k] + (1 - B1) * gk
            vk = B2 * v[k] + (1 - B2) * gk * gk
            mh = mk / (1 - B1 ** t)
            vh = vk / (1 - B2 ** t)
            pk = p[k].astype(jnp.float32)
            pk = pk - LR * (mh / (jnp.sqrt(vh) + EPS) + WD * pk)
            new_p[k] = pk.astype(p[k].dtype)
            new_m[k], new_v[k] = mk, vk
        return loss, new_p, new_m, new_v, t

    return jax.jit(step, donate_argnums=(0, 1, 2))


def run(variant):
    # an "unrolled_" prefix selects the python-loop forward (XLA schedules
    # its own memory; the scan form needs remat to fit long seq)
    unroll = variant == "unrolled" or variant.startswith("unrolled_")
    core = variant[len("unrolled_"):] if variant.startswith("unrolled_") \
        else variant
    kind = "xla_attn" if core == "xla_attn" else "flash"
    # block sweeps: flash_bq<N>, flash_bk<N>, flash_bq<N>k<M>
    bq = bk = None
    import re as _re

    mm = _re.match(r"flash_bq(\d+)(?:k(\d+))?$", core)
    if mm:
        bq = int(mm.group(1))
        bk = int(mm.group(2)) if mm.group(2) else None
    mm = _re.match(r"flash_bk(\d+)$", core)
    if mm:
        bk = int(mm.group(1))
    remat = {"remat": "full", "remat_dots": "dots"}.get(core, None)
    forward, forward_unrolled = make_forward(kind, bq=bq, bk=bk, remat=remat)
    step = make_step(forward_unrolled if unroll else forward)

    key = jax.random.PRNGKey(0)
    p = init_params(key)
    m = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
    v = {k: jnp.zeros(vv.shape, jnp.float32) for k, vv in p.items()}
    t = jnp.zeros((), jnp.int32)
    rng = np.random.default_rng(0)
    ids = jax.device_put(
        jnp.asarray(rng.integers(0, VOCAB, (BSZ, SEQ + 1)), jnp.int32)
    )
    x, y = ids[:, :-1], ids[:, 1:]

    t0 = time.time()
    loss, p, m, v, t = step(p, m, v, t, x, y)
    first = float(loss)
    compile_s = time.time() - t0
    loss, p, m, v, t = step(p, m, v, t, x, y)
    float(loss)

    # min-of-REPS windows: ambient host load only slows a window down
    reps = int(os.environ.get("BENCH_REPS", 2))
    dt = float("inf")
    last = first
    for _ in range(max(1, reps)):
        t1 = time.time()
        for _ in range(STEPS):
            loss, p, m, v, t = step(p, m, v, t, x, y)
        last = float(loss)
        dt = min(dt, time.time() - t1)
    tps = BSZ * SEQ * STEPS / dt
    print(f"{variant}: {tps:,.0f} tok/s | {dt / STEPS * 1e3:.1f} ms/step | "
          f"first loss {first:.3f} -> {last:.3f} | compile {compile_s:.0f}s")
    return tps


if __name__ == "__main__":
    variants = sys.argv[1:] or ["flash"]
    for vr in variants:
        run(vr)
