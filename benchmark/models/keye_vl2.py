"""The program's side of the ``keye_vl2`` configurations: the zoo's
decoder of grouped-query attention with learned sparse selection and
softmax-routed experts behind ``GenerationEngine``, built from the
configuration's keys. The plain reference is ``reference/keye_vl2.py``;
nothing here is shared with it. Every layer's experts are held whole.
"""

from benchmark.models.starcoder2 import _shell_init


def build_shell(cfg: dict, max_length: int):
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo import SparseGQAMoETransformer

    net = ComputationGraph(
        SparseGQAMoETransformer(cfg, max_length=max_length).conf())
    return net, _shell_init(net)
