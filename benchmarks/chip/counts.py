"""Operations and bytes that serving needs, counted from a config's shapes.

These are what the algorithm needs, not what the program happens to
execute: causal attention counts only the keys at or before each query,
logits are counted only at positions that sample a token, and padding
and recomputation are not counted.  Each dot of one ``generate`` call
(prefill of ``batch`` prompts, then ``new - 1`` decode steps) is listed
with its operations and its least bytes: each weight read once per
step, activations read and written once, the KV cache read at its stored
type.  ``mfu`` and ``generate_roofline`` read them.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int          # hidden size
    h: int          # query heads
    hkv: int        # key/value heads
    hd: int         # head size
    ff: int         # MLP width
    vocab: int
    layers: int

    @property
    def q(self) -> int:
        return self.h * self.hd

    @property
    def kv(self) -> int:
        return self.hkv * self.hd


def dims(conf: dict) -> Dims:
    h = conf["num_attention_heads"]
    return Dims(d=conf["hidden_size"], h=h, hkv=conf["num_key_value_heads"],
                hd=conf.get("head_dim") or conf["hidden_size"] // h,
                ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                layers=conf["num_hidden_layers"])


@dataclasses.dataclass(frozen=True)
class Dot:
    name: str
    flops: float
    bytes: float
    count: int = 1       # identical dots (layers, steps)


def _mm(name, m, k, n, count, w_bytes=2, a_bytes=2):
    """An (m, k) x (k, n) product against a weight read once."""
    return Dot(name, 2.0 * m * k * n,
               float(k * n * w_bytes + (m * k + m * n) * a_bytes), count)


def _projections(t: Dims, m: int, count: int, tag: str):
    return [_mm(f"{tag}.q", m, t.d, t.q, count),
            _mm(f"{tag}.k", m, t.d, t.kv, count),
            _mm(f"{tag}.v", m, t.d, t.kv, count),
            _mm(f"{tag}.o", m, t.q, t.d, count),
            _mm(f"{tag}.gate_up", m, t.d, 2 * t.ff, count),
            _mm(f"{tag}.down", m, t.ff, t.d, count)]


def generate_dots(conf: dict, batch: int, prompt: int, new: int,
                  kv_bytes: int = 2, a_bytes: int = 2) -> "list[Dot]":
    """Every dot of one ``generate`` call: prefill of ``batch`` prompts
    of ``prompt`` tokens, then ``new - 1`` decode steps."""
    t = dims(conf)
    L, b = t.layers, batch
    dots = _projections(t, b * prompt, L, "prefill")
    # Causal attention over the prompt: query i sees i + 1 keys.
    # QK reads queries and keys; PV reads values and writes the output.
    pairs = b * t.h * prompt * (prompt + 1) / 2
    io = b * prompt * (t.q + t.kv) * a_bytes
    dots += [Dot("prefill.qk", 2.0 * pairs * t.hd, io, L),
             Dot("prefill.pv", 2.0 * pairs * t.hd, io, L)]
    dots.append(_mm("prefill.head", b, t.d, t.vocab, 1))
    steps = new - 1
    if steps > 0:
        dots += _projections(t, b, L * steps, "decode")
        dots.append(_mm("decode.head", b, t.d, t.vocab, steps))
        # Step j (0-based) attends over prompt + j + 1 cached positions.
        ctx = steps * prompt + steps * (steps + 1) / 2
        cache = b * t.hkv * ctx * t.hd * kv_bytes
        dots += [Dot("decode.qk", 2.0 * b * t.h * ctx * t.hd,
                     cache + b * steps * t.q * a_bytes, L),
                 Dot("decode.pv", 2.0 * b * t.h * ctx * t.hd,
                     cache + b * steps * t.q * a_bytes, L)]
    return dots


def request_flops(conf: dict, prompt: int, new: int) -> float:
    """Model operations to serve one request: its share of a batch."""
    return sum(x.flops * x.count for x in generate_dots(conf, 1, prompt, new))


def least_time(dots, flops_per_s: float, bytes_per_s: float):
    """(least seconds of all ``dots``, the share of it set by compute)."""
    total = bound = 0.0
    for x in dots:
        c, m = x.flops / flops_per_s, x.bytes / bytes_per_s
        total += max(c, m) * x.count
        if c >= m:
            bound += c * x.count
    return total, (bound / total if total else 0.0)
