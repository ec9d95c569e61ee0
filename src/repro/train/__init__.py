from .losses import lm_loss
from .state import TrainState, node_solver_counts
from .train_step import (TrainConfig, init_train_state, make_grad_fn,
                         make_train_step)
from .serve_step import make_prefill_step, make_decode_step

__all__ = ["lm_loss", "TrainConfig", "TrainState", "make_grad_fn",
           "make_train_step",
           "init_train_state", "node_solver_counts", "make_prefill_step",
           "make_decode_step"]
