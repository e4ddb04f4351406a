"""Operations and bytes that the WORK needs, from shapes alone: what a
roofline share or an MFU is measured against. Nothing here looks at how
the program does it (padding, idle lanes, recomputation and block sizes
do not count). FLOPs count a multiply-add as 2."""
from __future__ import annotations


# ---------------------------------------------------------------------------
# decoder (serve)
# ---------------------------------------------------------------------------
def decoder_matmul_params(m):
    """Weights that every token passes through once (per layer: q, k, v,
    o and the two MLP matrices), without the embedding and the head."""
    E, F = m["embed"], m["mlp_hidden"]
    return m["layers"] * (4 * E * E + 2 * E * F)


def decoder_position_flops(m, context):
    """One position through all layers, attending over `context`
    positions (itself included): matmuls, QK^T and PV."""
    return 2 * decoder_matmul_params(m) \
        + m["layers"] * 4 * context * m["embed"]


def decoder_head_flops(m):
    return 2 * m["vocab"] * m["embed"]


def decoder_request_flops(m, prompt, out):
    """A whole request: `prompt` tokens prefilled, `out` tokens served.
    Positions 0 .. prompt+out-2 go through the layers (the last served
    token is never fed back); the head runs once per served token."""
    n = prompt + out - 1
    layers = 2 * decoder_matmul_params(m) * n \
        + m["layers"] * 4 * m["embed"] * (n * (n + 1) // 2)
    return layers + out * decoder_head_flops(m)


def paged_attention_request_work(m, prompt, out, window, kv_itemsize=2):
    """(flops, bytes) that the paged-attention read owes one request: the
    prompt's chunks after its first window (that window goes through the
    dense prefill program, which has no such kernel) and its out-1 decode
    steps. A chunk at offset o with n positions multiplies each query
    with the o+j+1 positions it may see and reads K and V of [0, o+n)
    once; a decode step at cache length c reads c+1 positions."""
    L, E = m["layers"], m["embed"]
    flops = byts = 0
    o = min(prompt, window)
    while o < prompt:
        n = min(window, prompt - o)
        seen = n * o + n * (n + 1) // 2
        flops += 4 * E * seen
        byts += 2 * (o + n) * E * kv_itemsize
        o += n
    steps = out - 1
    seen = steps * (prompt + 1) + steps * (steps - 1) // 2
    flops += 4 * E * seen
    byts += 2 * seen * E * kv_itemsize
    return L * flops, L * byts


# ---------------------------------------------------------------------------
# ResNet v1 bottleneck (train)
# ---------------------------------------------------------------------------
def resnet_layers(m):
    """[(role, out_h, out_w, cin, cout, k)] of every convolution (roles
    stem, a, b, c, down) and then the dense layer, at the configuration's
    input size."""
    hw = (m["input_hw"] + 2 * 3 - 7) // 2 + 1
    out = [("stem", hw, hw, m["in_channels"], m["stem_channels"], 7)]
    hw = (hw + 2 - 3) // 2 + 1                       # max pool 3x3/2
    cin = m["stem_channels"]
    for s, (blocks, cout) in enumerate(zip(m["blocks"], m["channels"])):
        mid = cout // 4
        for b in range(blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            ohw = (hw - 1) // stride + 1
            out.append(("a", ohw, ohw, cin, mid, 1))
            out.append(("b", ohw, ohw, mid, mid, 3))
            out.append(("c", ohw, ohw, mid, cout, 1))
            if b == 0:
                out.append(("down", ohw, ohw, cin, cout, 1))
            cin, hw = cout, ohw
    out.append(("dense", 1, 1, cin, m["classes"], 1))
    return out


def resnet_forward_flops(m):
    return sum(2 * h * w * cin * cout * k * k
               for _, h, w, cin, cout, k in resnet_layers(m))


def resnet_train_flops_per_image(m):
    """Forward, and a backward of twice the forward (gradients of the
    inputs and of the weights): 3 x forward, `bench.py`'s accounting."""
    return 3 * resnet_forward_flops(m)


def resnet_bn_apply_bytes_per_image(m, itemsize=2):
    """Bytes that the forward's scale/shift/activation passes must move:
    each convolution's output read once and written once, and the
    residual read once more where a block ends, in the activations' type.
    Memory-bound by construction (a few operations per element)."""
    elems = 0
    for role, h, w, _cin, cout, _k in resnet_layers(m):
        if role == "dense":
            continue
        elems += (3 if role == "c" else 2) * h * w * cout
    return elems * itemsize
