"""The program's side of the ``deepseek_v32`` configurations: the zoo's
latent-attention / routed-expert decoder behind ``GenerationEngine``, built
from the configuration's keys. The plain reference is
``reference/deepseek_v32.py``; nothing here is shared with it.

``n_routed_experts`` of a cut configuration counts the experts held on
this chip (experts 0 .. n - 1); the router keeps the width
``published.n_routed_experts``.
"""

from benchmark.models.starcoder2 import _shell_init


def build_shell(cfg: dict, max_length: int):
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo import SparseLatentMoETransformer

    held = cfg["n_routed_experts"]
    zoo = SparseLatentMoETransformer(
        cfg, max_length=max_length, held_experts=(0, held),
        router_experts=cfg.get("published", {}).get("n_routed_experts",
                                                    held))
    net = ComputationGraph(zoo.conf())
    return net, _shell_init(net)
