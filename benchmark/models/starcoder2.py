"""The program's side of the ``starcoder2`` configurations: the zoo
transformer the serving cells put behind ``GenerationEngine``, built from
the configuration's sizes. The plain reference is
``reference/starcoder2.py``; nothing here is shared with it.

A served model's file gives ``build_shell(cfg, max_length)``: the net,
initialised without drawing a weight, and the shapes of its parameter
tree; the runner installs the seed's bfloat16 leaves by name.
"""

#: what ``_shell_init`` reaches into, for lack of a public way to
#: initialise a graph without drawing its float32 weights (PERF.md, Open
#: questions): checked by name, so that a rename fails here and loudly
GRAPH_INTERNALS = ("_infer_types", "_topo", "_vertex_input_types", "_rng",
                   "_initialized")


def build_shell(cfg: dict, max_length: int):
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo import TextGenerationTransformer

    heads = cfg["num_attention_heads"]
    if cfg["hidden_size"] != heads * cfg["head_dim"] or \
            cfg["intermediate_size"] % cfg["hidden_size"]:
        raise ValueError("the zoo transformer ties head_dim to "
                         "hidden_size / heads and the FFN width to a "
                         "whole multiple of hidden_size")
    zoo = TextGenerationTransformer(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        n_heads=heads, n_layers=cfg["num_hidden_layers"],
        ffn_mult=cfg["intermediate_size"] // cfg["hidden_size"],
        max_length=max_length, positional="rope",
        n_kv_heads=cfg["num_key_value_heads"])
    conf = zoo.conf()
    conf.dtype = cfg["torch_dtype"]
    for v in conf.vertices.values():
        layer = getattr(v, "layer", None)
        if isinstance(layer, SelfAttentionLayer):
            layer.rope_base = cfg["rope_theta"]
        if hasattr(layer, "eps"):
            layer.eps = cfg["norm_epsilon"]
    net = ComputationGraph(conf)
    return net, _shell_init(net)


def _shell_init(net):
    """``net.init()`` without drawing a weight: the float32 masters it
    would make (12.7 GB at these sizes) never exist. Shapes come from
    each layer's own ``init`` under ``eval_shape``."""
    import jax
    missing = [a for a in GRAPH_INTERNALS if not hasattr(net, a)]
    if missing or net._initialized:
        raise RuntimeError(
            f"ComputationGraph no longer has {missing or 'a fresh state'}:"
            f" the benchmark's weightless init must be rewritten against "
            f"the program (benchmark/models/starcoder2.py)")
    net._infer_types()
    key = jax.random.PRNGKey(0)
    shapes, params, state = {}, {}, {}
    for name in net._topo:
        v = net.conf.vertices[name]
        p, s = jax.eval_shape(
            lambda k, v=v, name=name: v.init(
                k, net._vertex_input_types[name]), key)
        if jax.tree_util.tree_leaves(s):
            raise RuntimeError(f"vertex {name} carries initial state "
                               f"the shell init cannot make: {s}")
        shapes[name] = p
        params[name], state[name] = {}, {}
    net.params, net.state, net.updater_state = params, state, {}
    net._rng = jax.random.PRNGKey(1)
    net._initialized = True
    return shapes
