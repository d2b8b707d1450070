"""Operations and bytes that a model's work needs, from its shapes.

Every count is of the work the model needs, not of what an implementation
happens to run: a prompt that is scored needs the head at its last
position only, and causal attention needs only the live (query, key)
pairs.  A contraction's bytes are its operands at two bytes (bfloat16, as
the configurations state) and its output at the size of the dtype it is
kept in.  Elementwise work (norms, activations, the SSM's decay, the
depthwise convolution) is not counted.

``cfg`` is a configuration dict as ``bench/configs/<name>.json`` holds it.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Work:
    """Operations and bytes of some contractions; ``parts`` keeps each
    contraction's own (flops, bytes, times run), since each is bound by
    its own roofline."""
    flops: float = 0.0
    bytes: float = 0.0
    parts: tuple = ()

    def __add__(self, o):
        return Work(self.flops + o.flops, self.bytes + o.bytes,
                    self.parts + o.parts)

    def __mul__(self, k):
        return Work(self.flops * k, self.bytes * k,
                    tuple((f, b, n * k) for f, b, n in self.parts))

    def least_seconds(self, peak_flops, peak_bytes_per_s):
        """The roofline: each contraction's larger of its compute and its
        memory bound, summed."""
        return sum(n * max(f / peak_flops, b / peak_bytes_per_s)
                   for f, b, n in self.parts)


def gemm(m, n, k, *, batch=1, out_bytes=2) -> Work:
    """(batch x) an (m, k) by (k, n) contraction."""
    f = 2.0 * batch * m * n * k
    b = batch * (2.0 * (m * k + k * n) + out_bytes * m * n)
    return Work(f, b, ((f, b, 1),))


def causal_attention(b, s, heads, kv_heads, head_dim) -> Work:
    """Scores and values over the s(s+1)/2 live pairs of each head; Q and
    O of every head, K and V of every KV head, once each."""
    pairs = s * (s + 1) / 2
    f = 4.0 * b * heads * pairs * head_dim
    by = 2.0 * b * s * head_dim * (2 * heads + 2 * kv_heads)
    return Work(f, by, ((f, by, 1),))


def _sum(works):
    total = Work()
    for w in works:
        total = total + w
    return total


def head(cfg, rows) -> Work:
    return gemm(rows, cfg["vocab_size"], cfg["d_model"], out_bytes=4)


# ---------------------------------------------------------------- mamba2

def _mamba2_dims(cfg):
    d_in = cfg["ssm_expand"] * cfg["d_model"]
    return d_in, d_in // cfg["ssm_headdim"], cfg["ssm_state"], cfg[
        "ssm_headdim"]


def mamba2_layer(cfg, b, t) -> Work:
    """One layer over b sequences of t new positions.  t == 1 is a decode
    step: the recurrent state update B x^T and the read-out C . state.
    Longer t runs the published chunked SSD (chunk ``ssm_chunk``)."""
    d = cfg["d_model"]
    d_in, h, n, p = _mamba2_dims(cfg)
    proj = gemm(b * t, 2 * d_in + 2 * n + h, d) + gemm(b * t, d, d_in)
    if t == 1:
        ssd = (gemm(n, h * p, 1, batch=b, out_bytes=4)
               + gemm(1, h * p, n, batch=b))
        return proj + ssd
    c = min(cfg["ssm_chunk"], t)
    nc = b * (t // c)
    ssd = _sum([gemm(c, c, n, batch=nc, out_bytes=4),          # C B^T
                gemm(c, p, c, batch=nc * h),                   # intra-chunk
                gemm(n, h * p, c, batch=nc, out_bytes=4),      # chunk states
                gemm(c, h * p, n, batch=nc)])                  # state out
    return proj + ssd


# ----------------------------------------------------------------- dense

def dense_layer_gemms(cfg, rows) -> Work:
    d, f, hd = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    q, kv = cfg["num_heads"] * hd, cfg["num_kv_heads"] * hd
    return _sum([gemm(rows, q, d), gemm(rows, kv, d), gemm(rows, kv, d),
                 gemm(rows, d, q), gemm(rows, f, d), gemm(rows, f, d),
                 gemm(rows, d, f)])


def dense_layer_attention(cfg, b, t) -> Work:
    return causal_attention(b, t, cfg["num_heads"], cfg["num_kv_heads"],
                            cfg["head_dim"])


# ------------------------------------------------------ the jobs' units

def contractions(cfg, b, t, *, head_rows) -> Work:
    """Every contraction but attention of a forward over b x t positions,
    with the head at ``head_rows`` rows."""
    layers = cfg["num_layers"]
    if cfg["family"] == "ssm":
        body = mamba2_layer(cfg, b, t)
    else:
        body = dense_layer_gemms(cfg, b * t)
    return body * layers + head(cfg, head_rows)


def attention(cfg, b, t) -> Work:
    if cfg["family"] == "ssm":
        return Work()
    return dense_layer_attention(cfg, b, t) * cfg["num_layers"]


def forward(cfg, b, t, *, head_rows) -> Work:
    return contractions(cfg, b, t, head_rows=head_rows) + attention(cfg, b,
                                                                      t)


def generate_batch(cfg, batch, prompt_len, gen_len) -> tuple[Work, Work]:
    """(contractions, attention) of one generated batch: the prompt's
    prefill with the head at the last position, then gen_len - 1 decode
    steps (the prefill gives the first token)."""
    pre = contractions(cfg, batch, prompt_len, head_rows=batch)
    step = contractions(cfg, batch, 1, head_rows=batch)
    return pre + step * (gen_len - 1), attention(cfg, batch, prompt_len)


def score_prompt(cfg, prompt_len) -> tuple[Work, Work]:
    """(contractions, attention) of one scored prompt: the next-token
    logits need the head at the last position only."""
    return (contractions(cfg, 1, prompt_len, head_rows=1),
            attention(cfg, 1, prompt_len))


def train_step(cfg, batch, seq) -> Work:
    """A training step: the forward at every position, and a backward of
    twice its operations.  Recomputation is not counted."""
    return forward(cfg, batch, seq, head_rows=batch * seq) * 3
