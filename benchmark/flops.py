"""Operations and bytes from shapes: what the algorithm needs, not what a
compiler or a kernel happens to execute.

Every function takes a configuration's ``sizes`` (the builder's keyword
arguments) and returns floating-point operations; a multiply-add is two.
Training costs forward + backward = 3 x forward for every matmul and
convolution (one product forward, two for the two gradients); nothing
recomputed is counted.  A configuration names its function as
``"flops": "<module>:<function>"`` (module relative to ``benchmark/``), so
a later architecture brings a new file and edits none.
"""


def gpt_lm_matmul_flops_per_token(sizes: dict) -> float:
    """Forward matmul FLOPs of one token through ``zoo.gpt_lm``: in every
    block qkv (d x 3d), out (d x d) and the FF pair (d x f, f x d), then
    the d x V head (not tied to the embedding, which is a gather)."""
    d, v = sizes["dim"], sizes["vocab_size"]
    f = d * sizes.get("ff_mult", 4)
    block = 2 * d * 3 * d + 2 * d * d + 2 * (2 * d * f)
    return float(sizes["num_blocks"] * block + 2 * d * v)


def causal_attention_flops_per_token(sizes: dict) -> float:
    """Forward QK^T and PV FLOPs of one token, averaged over a sequence,
    for causal attention at its executed half: a query at position i
    needs i + 1 keys, (T + 1) / 2 on average."""
    t, d = sizes["seq_len"], sizes["dim"]
    return float(sizes["num_blocks"] * 2 * (2 * d) * (t + 1) / 2)


def gpt_lm_train(sizes: dict) -> float:
    """Forward + backward FLOPs of one row (one ``seq_len`` sequence)."""
    per_token = 3 * (gpt_lm_matmul_flops_per_token(sizes)
                     + causal_attention_flops_per_token(sizes))
    return per_token * sizes["seq_len"]


#: matmuls of T x T x head_dim that causal flash attention needs: two
#: forward (S = QK^T, PV); five backward (S again, because the algorithm
#: keeps no T x T matrix, then dP = dO V^T, dV, dK, dQ)
FLASH_MATMULS = {"fwd": 2, "bwd": 5}

#: what THIS repo's kernels run instead (``ops/pallas_attention.py``): the
#: backward is two kernels, dQ (S, dP, dQ) and dK/dV (S, dP, dV, dK)
FLASH_MATMULS_EXECUTED = {"fwd": 2, "bwd": 7}


def flash_train(sizes: dict, batch: int) -> tuple:
    """(FLOPs, bytes) that the flash calls of ONE training step need:
    every block's forward and backward over ``batch`` rows, causal, at the
    exact causal half.  Bytes are each operand once, in bf16: forward
    reads q, k, v and writes o; backward reads q, k, v, o, dO and writes
    dq, dk, dv (the float32 row statistics are T x 4 bytes a head and
    left out)."""
    t, d = sizes["seq_len"], sizes["dim"]
    pairs = t * (t + 1) / 2
    matmuls = FLASH_MATMULS["fwd"] + FLASH_MATMULS["bwd"]
    flops = sizes["num_blocks"] * batch * matmuls * 2 * d * pairs
    bytes_ = sizes["num_blocks"] * batch * (4 + 8) * t * d * 2
    return float(flops), float(bytes_)


def flash_executed_block_pairs(t: int, block_q: int, block_k: int) -> int:
    """(q block, k block) pairs a causal kernel with these blocks runs:
    every pair with at least one key at or before the block's last query.
    With one T x T block (``_auto_block(1024, 64)`` = 1024) that is the
    whole square, twice the causal half."""
    return sum(1 for qi in range(t // block_q) for kb in range(t // block_k)
               if kb * block_k <= qi * block_q + block_q - 1)


def resnet_cifar_macs(sizes: dict) -> int:
    """Forward multiply-adds of one 32x32x3 image through
    ``zoo.resnet20``: He et al. 2016 section 4.2 with n = 3, 3x3
    convolutions, and 1x1 projection shortcuts where a stage changes
    shape (the zoo's choice; the paper's CIFAR nets pad instead)."""
    w, n, classes = sizes["width"], 3, sizes.get("num_classes", 10)
    side, macs, cin = 32, 32 * 32 * 9 * 3 * w, w
    for stage, cout in enumerate((w, 2 * w, 4 * w)):
        if stage:
            side //= 2
            macs += side * side * cin * cout            # 1x1 shortcut
        macs += side * side * 9 * cin * cout            # first conv
        macs += (2 * n - 1) * side * side * 9 * cout * cout
        cin = cout
    return macs + cin * classes


def resnet_cifar_train(sizes: dict) -> float:
    """Forward + backward FLOPs of one row (one image)."""
    return float(3 * 2 * resnet_cifar_macs(sizes))
