"""Long-context language modeling, single chip to sequence-parallel mesh.

The reference's sequence ceiling was one worker's LSTM (SURVEY.md §5.7).
This example trains a GPT-style causal LM (``zoo.gpt_lm``) on a
character-counting corpus and walks the long-context ladder:

    1. dense attention      — XLA-fused O(T²) reference path
    2. flash attention      — Pallas VMEM-resident kernels, O(T·D) HBM
                              (fwd AND bwd), single chip
    3. remat                — jax.checkpoint around the forward: trade
                              FLOPs for activation memory
    4. ring attention       — sequence sharded over an ``sp`` mesh,
                              K/V rotating via ppermute (past-one-chip)

Runs anywhere: on TPU the mesh rides ICI; on CPU export
``XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu``.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import distkeras_tpu as dk
from distkeras_tpu.models import zoo
from distkeras_tpu.ops.attention import MultiHeadAttention
from distkeras_tpu.parallel.mesh import make_mesh

VOCAB, SEQ = 64, 256
# sized for one TPU chip; shrink for CPU smoke runs, e.g.
#   DK_LM_ROWS=256 DK_LM_EPOCHS=1 DK_LM_DIM=32
ROWS = int(os.environ.get("DK_LM_ROWS", 2048))
EPOCHS = int(os.environ.get("DK_LM_EPOCHS", 4))
DIM = int(os.environ.get("DK_LM_DIM", 128))


def corpus(n=ROWS, seq=SEQ, vocab=VOCAB, seed=0):
    """Next token = (current + 1) mod vocab; targets = inputs shifted."""
    from distkeras_tpu.data.datasets import load_lm_corpus
    return load_lm_corpus(n_train=n, seq_len=seq, vocab_size=vocab,
                          seed=seed)[0]


def token_accuracy(model, ds):
    logits = jax.jit(model.predict_fn())(model.variables,
                                         jnp.asarray(ds["features"][:256]))
    pred = np.asarray(jnp.argmax(logits, axis=-1))
    return float((pred == ds["label"][:256]).mean())


def main():
    ds = corpus()
    print(f"corpus: {ds['features'].shape[0]} sequences × {SEQ} tokens, "
          f"vocab {VOCAB}")

    # -- 1+2+3. single chip: dense vs flash attention, with remat ----------
    for impl, remat in (("dense", False), ("flash", False), ("flash", True)):
        t = dk.SingleTrainer(
            zoo.gpt_lm(vocab_size=VOCAB, dim=DIM, num_heads=4,
                       num_blocks=2, seq_len=SEQ, attention_impl=impl),
            "adam", "sparse_categorical_crossentropy",
            features_col="features", label_col="label",
            num_epoch=EPOCHS, batch_size=64, learning_rate=3e-3,
            remat=remat)
        t0 = time.time()
        m = t.train(ds)
        acc = token_accuracy(m, ds)
        print(f"attention={impl:5s} remat={remat}: next-token acc "
              f"{acc:.3f}, {time.time() - t0:.1f}s")

    # greedy generation from the last trained model: the continuation
    # should follow the corpus rule (next = current + 1 mod vocab)
    prompt = jnp.asarray(ds["features"][:2, :8])
    out = dk.generate_tokens(m, m.variables, prompt, num_steps=12)
    print(f"prompt {np.asarray(prompt[0, -4:]).tolist()} -> generated "
          f"{np.asarray(out[0, 8:]).tolist()}")

    # RAGGED prompts decode KV-cached too (r5): right-pad, pass lengths —
    # each row continues its own count from its own last content token
    ragged = np.asarray(ds["features"][:2, :8]).copy()
    lens = np.array([8, 5], np.int32)
    ragged[1, 5:] = 0
    out = dk.generate_tokens(m, m.variables, jnp.asarray(ragged),
                             num_steps=6, prompt_lengths=lens)
    print(f"ragged row (len 5) {ragged[1, :5].tolist()} -> generated "
          f"{np.asarray(out[1, 5:11]).tolist()}")

    # -- 4. sequence-parallel: ring attention over an sp mesh --------------
    n_dev = len(jax.devices())
    if n_dev >= 2 and SEQ % n_dev == 0:
        model = zoo.gpt_lm(vocab_size=VOCAB, dim=DIM, num_heads=4,
                           num_blocks=2, seq_len=SEQ)
        mesh = make_mesh(n_dev, ("sp",))
        for layer in model.iter_layers():
            if isinstance(layer, MultiHeadAttention):
                layer.mesh = mesh
        t = dk.SingleTrainer(model, "adam",
                             "sparse_categorical_crossentropy",
                             features_col="features", label_col="label",
                             num_epoch=EPOCHS, batch_size=64,
                             learning_rate=3e-3)
        m = t.train(ds)
        # causal + mesh => the load-balanced ZIGZAG ring layout engages
        # automatically (every device does equal work per hop; the
        # contiguous layout's straggler shard computed ~2x the average)
        print(f"ring attention over {n_dev}-way sp mesh (zigzag causal "
              f"layout): next-token acc {token_accuracy(m, ds):.3f}")
    else:
        print(f"({n_dev} device(s): skipping the ring-attention stage — "
              f"run with the 8-device CPU mesh env to see it)")


if __name__ == "__main__":
    main()
