"""Names of the training step's phases, as ``jax.named_scope`` scopes.

Each name is written into the name stack of the operations traced under
it, and from there into the compiled HLO's ``metadata={op_name=...}``, so
a device trace can attribute an op to its phase.  An op that belongs to the
backward of a scope carries ``transpose(jvp(<name>))`` in its name stack.

* ``ODE_SOLVE``          — ``core.api.solve``: the forward solve; its
  backward (the symplectic replay, remat's recompute and VJPs) comes out
  under ``transpose(jvp(ode_solve))``.
* ``ADJOINT_ACCUMULATE`` — ``core.symplectic``: the sums of the parameter
  gradient over the stages and steps of Algorithm 2.
* ``ATTENTION``          — ``kernels.ops.attention``, kernel or jnp.
* ``LM_LOSS``            — ``train.losses``: head and cross entropy.
* ``OPTIMIZER``          — ``optim``: global-norm clip and AdamW.
"""

ODE_SOLVE = "ode_solve"
ADJOINT_ACCUMULATE = "adjoint_accumulate"
ATTENTION = "attention"
LM_LOSS = "lm_loss"
OPTIMIZER = "optimizer"
