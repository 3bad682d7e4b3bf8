"""Device kernels and their oracles.  Import each from its own module:

  route_core        the block-greedy routing core (hash, candidate fetch,
                    masked argmin, water-fill, histogram update) that the
                    chunk step, the sharded router and the MoE dispatch share
  ss_update         the chunk step's per-event Space-Saving pass, one Pallas
                    kernel per chunk
  moe_pkg_dispatch  PKG / D- / W-Choices MoE expert dispatch (Pallas)
  flash_attention,
  rmsnorm           the LM scaffold's Pallas kernels
  ref               plain jnp oracles for every routing semantics and kernel
  platform          interpret-mode resolution: compiled on a TPU,
                    interpreted elsewhere
"""
