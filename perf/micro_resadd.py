"""Round-4 perf lever (d): is a fused residual-add epilogue worth a
custom kernel?

The round-3 bucket table attributes ~11 ms of the 43.76 ms step to
elementwise work (BN apply, ReLU masks, residual adds, SGD axpys) and
claims the residual adds are already fusion-neighbors of the convs.
Lever (d) (fused residual-add epilogue via custom_vjp on CAddTable) only
pays off if the add is NOT already fused — i.e. if removing it saves
more than its streaming-bandwidth cost.

This micro measures, on the bench shapes (b128, the layer3 bottleneck
exit: [128, 1024, 14, 14] bf16), fwd+bwd of
  (a) conv(1x1, 256->1024) + BN-apply + residual add + ReLU   (real block exit)
  (b) the same WITHOUT the residual add (+ ReLU directly)
differentially. The delta is the add's true
marginal cost; the streaming floor for one extra read of a
[128,1024,14,14] bf16 tensor at the measured 3 TB/s is ~0.02 ms. If
delta is at or below a few x the floor, XLA has already fused the add
into the conv epilogue and a custom_vjp kernel has nothing left to win.

Usage: python perf/micro_resadd.py   (needs a TPU)
"""
import time

import jax
import jax.numpy as jnp
import numpy as np


def timed_step(fn, args, n1=8, n2=72):
    def loop(n):
        @jax.jit
        def f(*a):
            def body(c, _):
                grads = fn(*c)
                # chain: feed grads back so iterations are dependent
                new_c = tuple((x - 1e-6 * g.astype(jnp.float32)).astype(x.dtype)
                              for x, g in zip(c, grads))
                return new_c, jnp.float32(0)

            c, _ = jax.lax.scan(body, tuple(a), None, length=n)
            return jnp.float32(c[0]).sum()

        return f

    f1, f2 = loop(n1), loop(n2)
    float(f1(*args)); float(f2(*args))
    # min each leg separately, then ONE difference (min-of-differences is
    # biased negative under host jitter)
    b1 = b2 = float("inf")
    for _ in range(6):
        t0 = time.perf_counter(); float(f1(*args)); b1 = min(b1, time.perf_counter() - t0)
        t0 = time.perf_counter(); float(f2(*args)); b2 = min(b2, time.perf_counter() - t0)
    return (b2 - b1) / (n2 - n1)


def main():
    b, cin, cout, hw = 128, 256, 1024, 14
    key = jax.random.key(0)
    x = jax.random.normal(key, (b, cin, hw, hw), jnp.float32).astype(jnp.bfloat16)
    res = jax.random.normal(key, (b, cout, hw, hw), jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(key, (1, 1, cin, cout), jnp.float32)
         / np.sqrt(cin)).astype(jnp.bfloat16)
    scale = jnp.ones((cout,), jnp.float32)
    bias = jnp.zeros((cout,), jnp.float32)

    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NCHW", "HWIO", "NCHW"))

    def block_with_add(x, w, res):
        def loss(x, w, res):
            y = conv(x, w)
            y = y * scale[:, None, None] + bias[:, None, None]
            y = jax.nn.relu(y + res)
            return jnp.float32(y).sum() * 1e-6

        g = jax.grad(loss, argnums=(0, 1, 2))(x, w, res)
        return g

    def block_no_add(x, w, res):
        def loss(x, w):
            y = conv(x, w)
            y = y * scale[:, None, None] + bias[:, None, None]
            y = jax.nn.relu(y)
            return jnp.float32(y).sum() * 1e-6

        g = jax.grad(loss, argnums=(0, 1))(x, w)
        return (*g, res)  # keep arity identical for the scan carry

    t_add = timed_step(block_with_add, (x, w, res))
    t_no = timed_step(block_no_add, (x, w, res))
    stream_floor = res.nbytes / 3e12  # one extra bf16 read at 3 TB/s
    print(f"fwd+bwd with residual add: {t_add * 1e3:.4f} ms")
    print(f"fwd+bwd without add:       {t_no * 1e3:.4f} ms")
    print(f"marginal add cost:         {(t_add - t_no) * 1e3:.4f} ms "
          f"(streaming floor {stream_floor * 1e3:.4f} ms)")
    ratio = (t_add - t_no) / stream_floor if stream_floor else float("inf")
    print(f"=> {ratio:.1f}x the one-extra-read floor; "
          + ("custom epilogue has headroom" if ratio > 4 else
         "already fused — custom_vjp epilogue has nothing to win"))


if __name__ == "__main__":
    main()
