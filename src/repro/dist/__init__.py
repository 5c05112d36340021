"""Distribution substrate: mesh-logical activation axes + placement.

``repro.dist.context`` binds the *logical* activation axes model code
references ("dp", "tp") to concrete mesh axes; ``repro.dist.sharding``
holds the placement policies (parameter, batch and cache specs) the
launchers feed to ``jax.jit``.
"""

from . import context, sharding

__all__ = ["context", "sharding"]
