"""Mixture-of-experts.  Parity: `python/paddle/incubate/distributed/models/moe/`."""

from .gate import BaseGate, GShardGate, NaiveGate, SwitchGate, capacity
from .moe_layer import ExpertMLP, MoELayer
from .dropless import HeldExpertsLayer, SigmoidTopKGate, SoftmaxTopKGate

__all__ = ["MoELayer", "ExpertMLP", "BaseGate", "NaiveGate", "SwitchGate",
           "GShardGate", "capacity", "SigmoidTopKGate", "SoftmaxTopKGate",
           "HeldExpertsLayer"]
