from .gate import BaseGate, GShardGate, NaiveGate, SwitchGate  # noqa: F401
from .held_moe import HeldExpertsMoE  # noqa: F401
from .moe_layer import (ExpertFFN, MoELayer, moe_a2a_chunks,  # noqa: F401
                        moe_fast_on)

__all__ = ["BaseGate", "GShardGate", "NaiveGate", "SwitchGate", "ExpertFFN",
           "MoELayer", "HeldExpertsMoE", "moe_fast_on", "moe_a2a_chunks"]
