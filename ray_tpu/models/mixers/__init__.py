"""The kinds of mixer a layer can have, one module each.

Adding a kind is one module here (its mathematics in the docstring, its
leaves, its checks, its `mix`, a `Mixer` record) and one line of `MIXERS`:
`transformer.py` reads the records and names no kind.
"""

from ray_tpu.models.mixers import attention, cca, diff_attention, dsa, gdn, gmu, kda, mamba2, mla, s6
from ray_tpu.models.mixers.base import Leaf, Mixer

# In this ORDER `TransformerConfig.stacks` lists a model's stacks, which
# fixes the key sequence each stack's weights are drawn from
# (`transformer.init_params`): append, do not insert.  The first kind is
# what a layer is when `layer_types` does not say.
MIXERS = {m.name: m for m in (
    attention.MIXER, mamba2.MIXER, kda.MIXER, mla.MIXER,
    s6.MIXER, diff_attention.MIXER, gmu.MIXER, diff_attention.CROSS, gdn.MIXER, dsa.SPARSE, dsa.WINDOW, cca.MIXER,
)}

__all__ = ["MIXERS", "Leaf", "Mixer"]
