"""PTB LSTM language-model training throughput on the bench chip.

The reference's only published LM number is an illustrative log of
~4.8 records/s early in PTB training (``DL/models/rnn/README.md:120-123``,
Spark CPU cluster). This measures the same workload shape on one TPU
chip with the repo's scan-based LSTM stack: batch of 20-token windows,
full fwd+bwd+Adagrad step, differential timing (same scheme as
bench.py).

Usage: python perf/lm_perf.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    from bigdl_tpu.models.rnn import build_ptb_lstm
    from bigdl_tpu.nn import TimeDistributedCriterion, ClassNLLCriterion
    from bigdl_tpu.optim.optim_method import Adagrad

    batch, seq_len, vocab = 128, 20, 10000
    model = build_ptb_lstm(vocab_size=vocab)
    crit = TimeDistributedCriterion(ClassNLLCriterion())
    method = Adagrad(learning_rate=0.1)

    params, mstate = model.init(jax.random.key(0))
    ostate = method.init_state(params)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randint(0, vocab, (batch, seq_len)), jnp.int32)
    y = jnp.asarray(rs.randint(0, vocab, (batch, seq_len)), jnp.int32)

    def step(carry, _):
        p, ms, os_ = carry

        def loss_fn(p):
            out, nms = model.apply(p, x, state=ms, training=True,
                                   rng=jax.random.key(1))
            return crit.forward(out.astype(jnp.float32), y), nms

        (loss, nms), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
        np_, nos = method.update(g, p, os_, jnp.int32(1))
        return (np_, nms, nos), loss

    def runner(n):
        @jax.jit
        def f(p, ms, os_):
            _, losses = jax.lax.scan(step, (p, ms, os_), None, length=n)
            return losses

        return f

    n1, n2 = 4, 20
    m1, m2 = runner(n1), runner(n2)
    l1 = np.asarray(m1(params, mstate, ostate))
    # TimeDistributedCriterion SUMS the per-step losses (reference
    # default, size_average=False) -> first-step loss ~ seq_len*ln(vocab)
    expect = seq_len * float(np.log(vocab))
    assert abs(float(l1[0]) - expect) < seq_len * 1.0, (float(l1[0]), expect)

    def timed(m, reps=10):
        np.asarray(m(params, mstate, ostate))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(m(params, mstate, ostate))
            best = min(best, time.perf_counter() - t0)
        return best

    dt = (timed(m2) - timed(m1)) / (n2 - n1)
    print(json.dumps({
        "metric": "ptb_lstm_train_records_per_sec",
        "value": round(batch / dt, 1),
        "unit": "records/sec (batch=128 of 20-token windows)",
        "ms_per_step": round(dt * 1e3, 2),
        "tokens_per_sec": round(batch * seq_len / dt, 1),
        "first_step_loss": round(float(l1[0]), 3),
        "platform": jax.devices()[0].platform,
        "reference_published": "~4.8 records/s (DL/models/rnn/README.md:120, Spark CPU)",
    }))


if __name__ == "__main__":
    main()
