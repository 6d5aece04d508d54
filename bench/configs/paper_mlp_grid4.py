"""The paper's MLP: the same plain reference as ``paper_mlp``."""
from bench.configs.paper_mlp import (flops_per_sample, init_params,  # noqa: F401
                                     logits, loss, make_data, shapes)
