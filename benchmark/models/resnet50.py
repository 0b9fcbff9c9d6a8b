"""The program's side of the ``resnet50`` configuration: the zoo net the
training cells drive, built from the configuration's sizes. The plain
reference is ``reference/resnet50.py``; nothing here is shared with it.

A training model's file gives ``build(cfg)`` (the initialised net, its
updater and compute type set; the runner installs the seed's weights),
``batches(cfg, rows, seed)`` (host arrays whose rows all differ) and
``first_gradient(cfg, updater_state)`` (traceable: the gradient the
optimizer got, worked out from its state after one step).
"""

import numpy as np


def build(cfg: dict):
    from deeplearning4j_tpu.nn.updater import Nesterovs
    from deeplearning4j_tpu.zoo import ResNet50

    upd = cfg["updater"]
    if upd["name"] != "nesterovs":
        raise ValueError(f"first_gradient reads Nesterov's velocity; the "
                         f"configuration names {upd['name']!r}")
    net = ResNet50(
        num_classes=cfg["num_classes"], height=cfg["image_size"],
        width=cfg["image_size"], channels=cfg["channels"],
        data_format=cfg["data_format"],
        updater=Nesterovs(upd["learning_rate"],
                          momentum=upd["momentum"])).init()
    net.conf.dtype = cfg["compute_dtype"]
    return net


def batches(cfg: dict, rows: int, seed: int):
    """``rows`` float32 NCHW images and one-hot labels from the seed."""
    rng = np.random.default_rng(seed)
    s = cfg["image_size"]
    x = rng.standard_normal((rows, cfg["channels"], s, s), dtype=np.float32)
    y = np.zeros((rows, cfg["num_classes"]), np.float32)
    y[np.arange(rows), rng.integers(0, cfg["num_classes"], rows)] = 1.0
    return x, y


def first_gradient(cfg: dict, updater_state):
    """After one Nesterov step from rest the velocity is ``-lr g``."""
    import jax
    lr = cfg["updater"]["learning_rate"]
    return jax.tree_util.tree_map(lambda v: -v / lr, updater_state["v"])
