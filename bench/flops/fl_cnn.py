"""Operation and byte counts of one SP-FL round on the paper CNN, from
shapes alone."""
from __future__ import annotations

# (output positions per image, multiply-adds per output) of each layer of
# the Section V CNN on 32x32x3 inputs
CNN_LAYERS = (
    (28 * 28 * 6, 5 * 5 * 3),     # conv1 5x5, 3 -> 6
    (10 * 10 * 16, 5 * 5 * 6),    # conv2 5x5, 6 -> 16
    (120, 400),                   # fc1
    (84, 120),                    # fc2
    (10, 84),                     # fc3
)
PARAMS = 62_006
WORD = 4


def macs_per_image() -> int:
    return sum(outputs * per for outputs, per in CNN_LAYERS)


def model_flops_per_round(config: dict, traffic: dict) -> float:
    """Forward and backward: 2 FLOPs per multiply-add, 3 passes (forward,
    input gradient, weight gradient), over every client's local batch."""
    images = int(traffic['clients']) * int(traffic['per_client'])
    return 6.0 * macs_per_image() * images


def wire_words(n: int, bits: int) -> tuple:
    """(sign packet words, modulus packet words) of one client: header,
    bit-plane payload in groups of 32 coordinates, xor-fold word."""
    groups = -(-n // 32)
    return 4 + groups + 1, 7 + groups * bits + 1


def kernel_work_per_round(config: dict, traffic: dict) -> tuple:
    """(bytes, flops) of the packed wire's kernel layer in one round, at
    unpadded sizes: the bit channel reads and writes every sign and
    modulus word, the verification fold reads them again, and the
    decode-once aggregate reads the payloads and the compensation vector
    and writes the n-float aggregate and the n sign votes.  The aggregate
    does a multiply-add to dequantize, a multiply to weight and an add to
    accumulate per coordinate and client; the channel's hash is integer
    work with no published peak and counts no FLOPs."""
    k = int(traffic['clients'])
    n = PARAMS
    bits = int(config['fl']['quant_bits'])
    ws, wm = wire_words(n, bits)
    payload = (ws - 5) + (wm - 8)
    channel = 2 * k * (ws + wm) * WORD
    verify = k * (ws + wm) * WORD
    aggregate = k * payload * WORD + 3 * n * WORD
    flops = 4.0 * k * n
    return float(channel + verify + aggregate), flops
