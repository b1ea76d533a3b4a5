"""The language models on the training path (docs/lm.md): one decoder
stack and its loss for every `config.DecoderConfig` (kanana-2, Trinity,
EvaByte, LFM2, SmallThinker, Nemotron-H), whose layers hold a mixer and
a feed-forward part or one of them alone, the mixers a configuration may
name (latent, grouped-query, EVA, short-convolution and state-space:
`attention.MIXERS`), and one expert layer that is told which experts it
holds, what its router reads and how it scores, its experts' form (two
or three matrices, their activation) and whether they work in a latent
space (`moe`).

Imported only by the paths that run it: `import dexiraft_tpu` and every
RAFT entry point leave this package alone.
"""

from dexiraft_tpu.models.lm.model import LM, head_loss, next_token_targets

__all__ = ["LM", "head_loss", "next_token_targets"]
