"""The five parallelism families on one mesh-sized machine.

The reference scales one way only — data parallelism over Spark executors
(SURVEY.md §2).  This framework keeps that surface and adds the
TPU-native axes; this example runs a small train step through each:

    dp     data parallelism        ADAG window collectives (shard_map)
    dp×mp  tensor parallelism      SpmdTrainer GSPMD sharding annotations
    sp     sequence parallelism    ring attention (ppermute K/V rotation)
    pp     pipeline parallelism    GPipe schedule (scan + ppermute)
    ep     expert parallelism      switch-MoE (all_to_all dispatch)

Runs anywhere: on a TPU pod each axis rides ICI; on CPU export
``XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu``
for the virtual 8-device mesh (the reference's Spark ``local[*]`` trick).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import distkeras_tpu as dk
from distkeras_tpu.data.transformers import OneHotTransformer
from distkeras_tpu.models.layers import Dense, Sequential
from distkeras_tpu.parallel.mesh import make_mesh
from distkeras_tpu.parallel.ring import ring_attention_sharded


def main():
    n = len(jax.devices())
    rng = np.random.default_rng(0)
    print(f"devices: {n}")

    # -- dp: the reference's strategy, one compiled SPMD epoch ------------
    train, _, _ = dk.datasets.load_mnist(n_train=n * 512)
    train = OneHotTransformer(10, "label", "label_onehot").transform(train)
    t = dk.ADAG(dk.zoo.mlp_mnist(hidden=64), "sgd", num_workers=n,
                communication_window=4, label_col="label_onehot",
                num_epoch=2, batch_size=64, learning_rate=0.05)
    t.train(train)
    print(f"dp    ADAG over {n} workers: "
          f"loss {t.get_averaged_history()[-1]:.3f}")

    # -- dp×mp: GSPMD tensor parallelism ----------------------------------
    mp = 2 if n % 2 == 0 else 1
    mlp = dk.Model(Sequential([Dense(256, "relu"), Dense(10, "softmax")]),
                   input_shape=(784,))
    st = dk.SpmdTrainer(mlp, "sgd", mesh_shape={"dp": n // mp, "mp": mp},
                        label_col="label_onehot", num_epoch=2,
                        batch_size=128, learning_rate=0.05)
    st.train(train)
    print(f"dp×mp GSPMD ({n // mp},{mp}) mesh: "
          f"loss {st.get_averaged_history()[-1]:.3f}")

    # -- sp: ring attention over a sequence too long for eager memory -----
    sp_mesh = make_mesh(n, ("sp",))
    q = jnp.asarray(rng.normal(size=(1, n * 128, 4, 16)), jnp.float32)
    out = ring_attention_sharded(sp_mesh, q, q, q, causal=True)
    print(f"sp    ring attention, T={q.shape[1]} over {n} shards: "
          f"out {tuple(out.shape)}")

    # -- pp: GPipe pipeline through the public PipelineTrainer -------------
    lm_ds = dk.datasets.load_lm_corpus(n_train=64, seq_len=32,
                                       vocab_size=17)[0]
    pp_shape = {"pp": n // 2, "dp": 2} if n % 2 == 0 and n >= 4 \
        else {"pp": n}
    pt = dk.PipelineTrainer(
        dk.zoo.gpt_lm(vocab_size=17, dim=32, num_heads=2,
                      num_blocks=max(2, pp_shape["pp"]), seq_len=32),
        "adam", "sparse_categorical_crossentropy", mesh_shape=pp_shape,
        num_microbatches=4, features_col="features", label_col="label",
        num_epoch=2, batch_size=16, learning_rate=1e-3)
    pt.train(lm_ds)
    print(f"pp    PipelineTrainer(gpt_lm) over {pp_shape}: "
          f"loss {pt.get_averaged_history()[-1]:.3f}")

    # -- ep: gpt_lm with ep-sharded switch-MoE FF blocks -------------------
    from distkeras_tpu.ops.moe import MoEDense
    ep_mesh = make_mesh(n, ("ep",))
    moe_model = dk.zoo.gpt_lm(vocab_size=17, dim=32, num_heads=2,
                              num_blocks=1, seq_len=32,
                              moe_experts=2 * n)
    for lyr in moe_model.iter_layers():
        if isinstance(lyr, MoEDense):
            lyr.mesh = ep_mesh
    et = dk.SingleTrainer(moe_model, "adam",
                          "sparse_categorical_crossentropy",
                          features_col="features", label_col="label",
                          num_epoch=2, batch_size=16, learning_rate=1e-3,
                          aux_weight=0.01)
    et.train(lm_ds)
    print(f"ep    gpt_lm({2 * n} experts) over {n} devices "
          f"(aux folded): loss {et.get_averaged_history()[-1]:.3f}")


if __name__ == "__main__":
    main()
