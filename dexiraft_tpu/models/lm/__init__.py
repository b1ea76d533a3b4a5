"""A DeepSeek-V3-style language model on the training path (docs/lm.md):
latent attention, a sigmoid-routed expert layer with shared experts that
is told which experts it holds, the decoder stack and its loss.

Imported only by the paths that run it: `import dexiraft_tpu` and every
RAFT entry point leave this package alone.
"""

from dexiraft_tpu.models.lm.model import LM, head_loss, next_token_targets

__all__ = ["LM", "head_loss", "next_token_targets"]
