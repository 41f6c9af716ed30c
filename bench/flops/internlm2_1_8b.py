"""Operations scoring one prompt requires, from the configuration's sizes.

One multiply-add counts 2 operations.  For a prompt of n tokens:

  projections: n * L * 2 * (D*H*hd + 2*D*K*hd + H*hd*D + 3*D*F)
  attention:   L * 4 * H * hd * n*(n+1)/2        causal q.k and p.v
  head:        2 * D * V                          one position: the next token

Padding to the service's length and recomputation are not counted.
"""


def forward_flops(m: dict, n: int) -> float:
    D, H, K, F, V, L = (m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"],
                        m["intermediate_size"], m["vocab_size"], m["num_hidden_layers"])
    hd = D // H
    proj = 2 * (D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F)
    return n * L * proj + L * 4 * H * hd * n * (n + 1) / 2 + 2 * D * V
