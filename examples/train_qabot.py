"""QA answer-selection training (reference examples/qabot/qabot_train.py):
encode question and candidate answers with biLSTMs, score by cosine
similarity, train with margin ranking loss over (positive, negative)
pairs, evaluate by top-1 accuracy over a candidate pool.

Runs on synthetic embedded data (the reference downloads the InsuranceQA
corpus + GloVe vectors; the model/training machinery is identical).
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def synthetic_qa(rng, n, seq_len, embed, n_topics=10):
    """Questions and answers about the same 'topic' share a direction in
    embedding space; the positive answer matches the question's topic."""
    topics = rng.randn(n_topics, embed).astype(np.float32)
    t = rng.randint(0, n_topics, n)
    t_neg = (t + 1 + rng.randint(0, n_topics - 1, n)) % n_topics
    q = topics[t][:, None, :] + 0.3 * rng.randn(n, seq_len, embed)
    a_pos = topics[t][:, None, :] + 0.3 * rng.randn(n, seq_len, embed)
    a_neg = topics[t_neg][:, None, :] + 0.3 * rng.randn(n, seq_len, embed)
    return (q.astype(np.float32), a_pos.astype(np.float32),
            a_neg.astype(np.float32))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kind", default="max",
                    choices=["lstm", "mean", "max", "mlp"])
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--bs", type=int, default=16)
    ap.add_argument("--n", type=int, default=128)
    ap.add_argument("--seq-len", type=int, default=10)
    ap.add_argument("--embed", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU backend (hermetic runs)")
    args = ap.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    from singa_tpu import device, opt, tensor
    from singa_tpu.models import qabot

    dev = device.create_cpu_device() if args.cpu \
        else device.create_tpu_device()
    dev.SetRandSeed(7)
    rng = np.random.RandomState(0)
    q, a_pos, a_neg = synthetic_qa(rng, args.n, args.seq_len, args.embed)

    m = qabot.create_model(args.kind, hidden_size=args.hidden)
    m.set_optimizer(opt.SGD(lr=args.lr, momentum=0.9))
    m.train()

    for epoch in range(args.epochs):
        idx = rng.permutation(args.n)
        t0, losses, correct = time.time(), [], 0
        for b in range(args.n // args.bs):
            sel = idx[b * args.bs:(b + 1) * args.bs]
            tq = tensor.Tensor(data=q[sel], device=dev,
                               requires_grad=False)
            ta = tensor.Tensor(
                data=np.concatenate([a_pos[sel], a_neg[sel]]),
                device=dev, requires_grad=False)
            sp, sn, loss = m.train_one_batch(tq, ta)
            losses.append(float(loss.data))
            correct += int((np.asarray(sp.data) >
                            np.asarray(sn.data)).sum())
        seen = (args.n // args.bs) * args.bs
        print(f"epoch {epoch}: loss {np.mean(losses):.4f} "
              f"top1 {correct / seen:.3f} "
              f"({seen / (time.time() - t0):.1f} pairs/s)")


if __name__ == "__main__":
    main()
