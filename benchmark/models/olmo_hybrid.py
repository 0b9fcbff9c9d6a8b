"""The program's side of the ``olmo_hybrid`` configurations: the zoo's
decoder of linear-attention and full-attention layers behind
``GenerationEngine``, built from the configuration's keys (the first
``num_hidden_layers`` entries of ``layer_types``). The plain reference is
``reference/olmo_hybrid.py``; nothing here is shared with it.
"""

from benchmark.models.starcoder2 import _shell_init


def build_shell(cfg: dict, max_length: int):
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo import HybridLinearTransformer

    net = ComputationGraph(
        HybridLinearTransformer(cfg, max_length=max_length).conf())
    return net, _shell_init(net)
