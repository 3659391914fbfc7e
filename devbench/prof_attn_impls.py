"""Compare attention implementations on the real chip at the bench geometry.

Contenders: our Pallas flash kernel, jax's bundled pallas flash_attention,
jax's splash attention, and plain XLA dot attention (materialized scores).
Each point is the per-step slope between two scan lengths.
"""
import functools
import time

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import flash_attention

B, S, H, KV, HD = 4, 2048, 32, 8, 64
L1, L2 = 8, 40


def timed_slope_chain(make_step, carry0, reps=5):
    def run_for(length):
        @jax.jit
        def run(c):
            def body(c, _):
                return make_step(c), None
            c, _ = lax.scan(body, c, None, length=length)
            return jax.tree_util.tree_reduce(
                lambda a, x: a + x.ravel()[0].astype(jnp.float32), c, 0.0)
        return run

    r1, r2 = run_for(L1), run_for(L2)
    float(r1(carry0)); float(r2(carry0))
    slopes = []
    for _ in range(reps):
        t0 = time.perf_counter(); float(r1(carry0)); t1 = time.perf_counter() - t0
        t0 = time.perf_counter(); float(r2(carry0)); t2 = time.perf_counter() - t0
        slopes.append((t2 - t1) / (L2 - L1))
    slopes.sort()
    return slopes[len(slopes) // 2]


key = jax.random.PRNGKey(0)
q = jax.random.normal(key, (B, H, S, HD), jnp.bfloat16)
k = jax.random.normal(key, (B, KV, S, HD), jnp.bfloat16)
v = jax.random.normal(key, (B, KV, S, HD), jnp.bfloat16)
cot = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, HD), jnp.bfloat16)
fl = 2 * 2 * B * H * S * S * HD / 2


def bench(name, fn, grow_kv=True):
    def fwd_step(c):
        qq, kk, vv = c
        o = fn(qq, kk, vv)
        return (qq + 1e-30 * o, kk, vv)

    def bwd_step(c):
        qq, kk, vv = c
        _, vjp = jax.vjp(fn, qq, kk, vv)
        dq, dk, dv = vjp(cot)
        return (qq + 1e-30 * dq, kk + 1e-30 * dk, vv + 1e-30 * dv)

    try:
        tf = timed_slope_chain(fwd_step, (q, k, v))
        print(f"{name:24s} fwd {tf*1e3:7.2f} ms {fl/tf/1e12:6.1f} TF/s",
              flush=True, end="  ")
    except Exception as e:  # noqa: BLE001
        print(f"{name:24s} fwd FAILED: {str(e)[:90]}", flush=True)
        return
    try:
        tb = timed_slope_chain(bwd_step, (q, k, v))
        print(f"| fwd+bwd {tb*1e3:7.2f} ms {3.5*fl/tb/1e12:6.1f} TF/s",
              flush=True)
    except Exception as e:  # noqa: BLE001
        print(f"| bwd FAILED: {str(e)[:90]}", flush=True)


import sys
WHICH = set(sys.argv[1:]) or {"ours", "dot", "jaxflash", "splash"}

if "ours" in WHICH:
    bench("ours(flash)", lambda a, b, c: flash_attention(a, b, c, causal=True))


def plain(qq, kk, vv):
    rep = H // KV
    kk = jnp.repeat(kk, rep, axis=1)
    vv = jnp.repeat(vv, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qq, kk,
                   preferred_element_type=jnp.float32) / (HD ** 0.5)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(vv.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv)


if "dot" in WHICH:
    bench("xla dot (materialized)", plain)

try:
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, flash_attention as jax_flash)

    def jf(qq, kk, vv):
        rep = H // KV
        kk = jnp.repeat(kk, rep, axis=1)
        vv = jnp.repeat(vv, rep, axis=1)
        return jax_flash(qq, kk, vv, causal=True, sm_scale=1.0 / HD ** 0.5)

    if "jaxflash" in WHICH:
        bench("jax pallas flash", jf)
except Exception as e:  # noqa: BLE001
    print("jax pallas flash unavailable:", str(e)[:90])

try:
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    mask = sm.MultiHeadMask(
        [sm.CausalMask((S, S)) for _ in range(H)])
    kernel = sk.make_splash_mha(mask=mask, head_shards=1, q_seq_shards=1)

    def spl(qq, kk, vv):
        rep = H // KV
        kk = jnp.repeat(kk, rep, axis=1)
        vv = jnp.repeat(vv, rep, axis=1)
        return jax.vmap(kernel)(qq, kk, vv)

    if "splash" in WHICH:
        bench("jax splash", spl)
except Exception as e:  # noqa: BLE001
    print("jax splash unavailable:", str(e)[:120])
