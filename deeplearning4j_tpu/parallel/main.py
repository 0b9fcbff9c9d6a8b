"""CLI entry point for mesh-parallel training.

Equivalent of deeplearning4j-scaleout main/ParallelWrapperMain.java:143
(JCommander args → ParallelWrapper training over a saved model + data).

Usage:
    python -m deeplearning4j_tpu.parallel.main \
        --model model.zip --data train.csv --label-index 4 \
        --num-classes 3 --batch-size 32 --epochs 5 \
        --training-mode allreduce --output trained.zip
"""

from __future__ import annotations

import argparse
import logging
import sys

log = logging.getLogger(__name__)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="deeplearning4j_tpu.parallel.main",
        description="Train a saved model data-parallel over the device "
                    "mesh (ParallelWrapperMain equivalent)")
    p.add_argument("--model", required=True,
                   help="model zip (ModelSerializer format)")
    p.add_argument("--data", required=True, help="training CSV")
    p.add_argument("--label-index", type=int, required=True)
    p.add_argument("--num-classes", type=int)
    p.add_argument("--regression", action="store_true")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--training-mode", default="allreduce",
                   choices=["allreduce", "averaging"])
    p.add_argument("--averaging-frequency", type=int, default=5)
    p.add_argument("--prefetch-buffer", type=int, default=2,
                   help="async prefetch depth (0 disables)")
    p.add_argument("--output", help="where to save the trained model zip")
    p.add_argument("--ui-port", type=int,
                   help="serve the training UI on this port")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    logging.basicConfig(level=logging.INFO)

    from deeplearning4j_tpu.datasets.records import (
        CSVRecordReader, RecordReaderDataSetIterator)
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    from deeplearning4j_tpu.util import model_serializer
    from deeplearning4j_tpu.util.compile_cache import (
        configure_compile_cache)

    configure_compile_cache()
    net = model_serializer.restore_model(args.model)
    it = RecordReaderDataSetIterator(
        CSVRecordReader(args.data), batch_size=args.batch_size,
        label_index=args.label_index, num_classes=args.num_classes,
        regression=args.regression)

    if args.ui_port is not None:
        from deeplearning4j_tpu.ui import (InMemoryStatsStorage,
                                           StatsListener, UIServer)
        storage = InMemoryStatsStorage()
        UIServer.get_instance(port=args.ui_port).attach(storage)
        net.add_listener(StatsListener(storage))

    pw = ParallelWrapper(net, training_mode=args.training_mode,
                         averaging_frequency=args.averaging_frequency,
                         prefetch_buffer=args.prefetch_buffer)
    pw.fit(it, epochs=args.epochs)
    log.info("final score: %s", net.score_value)
    if args.output:
        model_serializer.write_model(net, args.output)
        log.info("saved to %s", args.output)
    return 0


if __name__ == "__main__":
    sys.exit(main())
