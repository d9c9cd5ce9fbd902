"""CTC sequence training (parity: reference ``example/warpctc/`` —
LSTM + warp-CTC OCR on generated digit images; the loss here is the
built-in ``ctc_loss`` op, log-space scan replacing the vendored
warp-ctc kernels).

Task: images of LEN digits rendered as column-bar glyphs (each digit d
lights rows proportional to d in a noisy 12-row strip); the unsegmented
image scans left-to-right through an LSTM and CTC aligns the per-column
class posteriors with the digit sequence.  Greedy-decoded sequence
accuracy is the gate.

    python examples/warpctc_ocr.py [--num-epochs 12]
"""

import argparse
import logging
import os
import sys

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))

def _want_tpu(argv):
    for i, a in enumerate(argv):
        if a == "--tpus" and i + 1 < len(argv):
            return argv[i + 1] != "0"
        if a.startswith("--tpus="):
            return a.split("=", 1)[1] != "0"
    return False


if __name__ == "__main__" and not _want_tpu(sys.argv[1:]):
    # without --tpus this example asks for the CPU platform, before the
    # first backend touch (pass --tpus 1 to run on the chip)
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass

import mxnet_tpu as mx

ROWS = 12          # image height (input feature per column)
COLS_PER = 4       # columns per digit glyph
LEN = 3            # digits per image
N_DIGIT = 5        # digit alphabet 0..4 -> ctc classes 1..5, blank=0
T = LEN * COLS_PER + 4   # total columns (blank margins)
N_CLASS = N_DIGIT + 1    # + blank


def make_batch(rng, batch):
    """Images (batch, T, ROWS) + labels (batch, LEN) in 1..N_DIGIT."""
    imgs = rng.uniform(0, 0.15, (batch, T, ROWS)).astype(np.float32)
    labels = np.zeros((batch, LEN), np.float32)
    for b in range(batch):
        digits = rng.randint(0, N_DIGIT, LEN)
        labels[b] = digits + 1  # 0 is the CTC blank
        col = 2
        for d in digits:
            h = 2 + 2 * d  # bar height encodes the digit
            imgs[b, col:col + COLS_PER - 1, :h] += rng.uniform(0.7, 1.0)
            col += COLS_PER
    return imgs, labels


def get_symbol(num_hidden=32):
    data = mx.sym.Variable("data")            # (B, T, ROWS)
    label = mx.sym.Variable("label")          # (B, LEN)
    stack = mx.rnn.SequentialRNNCell()
    stack.add(mx.rnn.LSTMCell(num_hidden=num_hidden, prefix="lstm_"))
    outputs, _ = stack.unroll(T, inputs=data, layout="NTC",
                              merge_outputs=True)
    # per-timestep class scores: (B,T,H) -> (B*T,H) -> FC -> (T,B,C)
    flat = mx.sym.reshape(outputs, shape=(-1, num_hidden))
    pred = mx.sym.FullyConnected(flat, num_hidden=N_CLASS, name="cls")
    pred = mx.sym.reshape(pred, shape=(-1, T, N_CLASS))
    pred = mx.sym.transpose(pred, axes=(1, 0, 2))  # (T,B,C)
    loss = mx.sym.MakeLoss(mx.sym.mean(
        mx.contrib.sym.ctc_loss(pred, label)), name="ctc")
    # raw (T,B,C) scores for greedy decoding (argmax over C is invariant
    # to the softmax, so no activation needed on the inference head)
    scores = mx.sym.BlockGrad(pred, name="scores")
    return mx.sym.Group([loss, scores])


def greedy_decode(post):
    """(T,B,C) posteriors -> list of label sequences (collapse repeats,
    drop blanks)."""
    ids = post.argmax(axis=2)  # (T,B)
    out = []
    for b in range(ids.shape[1]):
        seq, prev = [], -1
        for t in range(ids.shape[0]):
            c = int(ids[t, b])
            if c != prev and c != 0:
                seq.append(c)
            prev = c
        out.append(seq)
    return out


def train(num_epochs=12, batch=32, lr=0.005, seed=0, ctx=None, log=True,
          stop_acc=None):
    ctx = ctx or mx.cpu()
    rng = np.random.RandomState(seed)
    np.random.seed(seed + 1)  # initializer stream
    sym = get_symbol()
    ex = sym.simple_bind(ctx, data=(batch, T, ROWS), label=(batch, LEN),
                         grad_req={n: ("null" if n in ("data", "label")
                                       else "write")
                                   for n in sym.list_arguments()})
    init = mx.initializer.Xavier()
    for name, arr in ex.arg_dict.items():
        if name not in ("data", "label"):
            init(mx.initializer.InitDesc(name), arr)
    opt = mx.optimizer.Adam(learning_rate=lr)
    updater = mx.optimizer.get_updater(opt)

    acc = 0.0
    for epoch in range(num_epochs):
        hits = tot = 0
        losses = []
        for _ in range(20):
            imgs, labels = make_batch(rng, batch)
            ex.arg_dict["data"][:] = imgs
            ex.arg_dict["label"][:] = labels
            ex.forward(is_train=True)
            ex.backward()
            for i, name in enumerate(sorted(ex.grad_dict)):
                g = ex.grad_dict[name]
                if g is not None:
                    updater(i, g, ex.arg_dict[name])
            outs = [o.asnumpy() for o in ex.outputs]
            losses.append(float(outs[0].mean()))
            decoded = greedy_decode(outs[1])
            want = [list(map(int, row)) for row in labels]
            hits += sum(1 for d, w in zip(decoded, want) if d == w)
            tot += batch
        acc = hits / tot
        if log:
            logging.info("epoch %d: ctc_loss=%.3f seq_acc=%.3f",
                         epoch, float(np.mean(losses)), acc)
        if stop_acc is not None and acc >= stop_acc:
            break
    return {"seq_acc": acc}


def main():
    logging.basicConfig(level=logging.INFO)
    p = argparse.ArgumentParser(description="CTC OCR training")
    p.add_argument("--num-epochs", type=int, default=12)
    p.add_argument("--tpus", type=int, default=0)
    args = p.parse_args()
    ctx = mx.tpu(0) if args.tpus else mx.cpu()
    stats = train(num_epochs=args.num_epochs, ctx=ctx)
    print("final:", stats)
    assert stats["seq_acc"] > 0.8, stats


if __name__ == "__main__":
    main()
