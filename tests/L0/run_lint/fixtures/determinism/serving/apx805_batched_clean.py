# apxlint: fixture
"""Known-clean APX805, the batched spelling: the seed root is read back
once when the request gets its slot, and one program folds every slot's
position counter into it — fold_in(PRNGKey(request seed), counter) for
all slots at once."""
import jax
import numpy as np


class Engine:
    def admit(self, slot, seed):
        self.base[slot] = np.asarray(jax.random.PRNGKey(seed))

    def step(self, slot, seed, counters, logits):
        self.admit(slot, seed)
        keys = jax.vmap(jax.random.fold_in)(self.base, counters)
        return jax.vmap(jax.random.categorical)(keys, logits)
