"""Seeds attention-program-budget under a declaration: the module's
layers have two attention kinds, so two attention program kinds are
within budget and the third is not."""
import jax

ATTENTION_KINDS = ("gqa", "mla")


def gqa_attention_step(q, k, v):
    return q


def latent_attention_step(q, c):
    return q


def decode_attention_step(q, k, v):
    return q


GQA = jax.jit(gqa_attention_step)
MLA = jax.jit(latent_attention_step)
DECODE = jax.jit(decode_attention_step)    # a third kind: over budget
