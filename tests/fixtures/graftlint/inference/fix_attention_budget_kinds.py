"""Seeds attention-program-budget under a declaration: the module's
layers have three attention kinds, so three attention program kinds are
within budget and the fourth is not."""
import jax

ATTENTION_KINDS = ("gqa", "mla", "gqa_window")


def gqa_attention_step(q, k, v):
    return q


def latent_attention_step(q, c):
    return q


def window_attention_step(q, k, v):
    return q


def decode_attention_step(q, k, v):
    return q


GQA = jax.jit(gqa_attention_step)
MLA = jax.jit(latent_attention_step)
WINDOW = jax.jit(window_attention_step)
DECODE = jax.jit(decode_attention_step)    # a fourth kind: over budget
