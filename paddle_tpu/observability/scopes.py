"""One vocabulary for the blocks of a served model's step program.

Every served model wraps its blocks in ``jax.named_scope(<name>)`` with
the names below, and ``GenerationServer`` wraps its sampler in
:data:`SAMPLE`.  A scope changes op metadata only, never an
instruction: the name lands in the ``op_name`` of every HLO op the
block lowers to (``jit(decode_fn)/moe/...``), which a device trace
carries as the op's ``tf_op``, so that the device time of a step can be
split by block at no device cost.

========  ============================================================
scope     holds
========  ============================================================
attn      softmax attention, whole: the norm before it (and after it,
          in a sandwich-norm model), the q/k/v or latent projections,
          rotary, the cache write, the attention, the output projection
mixer     a token mixer that is not softmax attention (the KDA update
          or chunk scan, a gated short convolution), with its norms
moe       router, routed experts, shared expert, combine, with the
          sublayer's norms
ffn       a dense FFN with the sublayer's norms
embed     the token embedding
head      the final norm, the gather of a prefill's last positions and
          the LM head (tied or not)
sample    the server's sampler
mtp       a multi-token-prediction module's own ops
========  ============================================================

Scopes may nest: an op's block is the INNERMOST vocabulary name on its
path, so the decoder layer inside an MTP module reads as its ``attn``
and ``moe``.
"""
__all__ = ["ATTN", "MIXER", "MOE", "FFN", "EMBED", "HEAD", "SAMPLE", "MTP",
           "VOCABULARY"]

ATTN = "attn"
MIXER = "mixer"
MOE = "moe"
FFN = "ffn"
EMBED = "embed"
HEAD = "head"
SAMPLE = "sample"
MTP = "mtp"

VOCABULARY = (ATTN, MIXER, MOE, FFN, EMBED, HEAD, SAMPLE, MTP)

