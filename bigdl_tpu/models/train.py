"""Unified training CLI for the model zoo.

Reference parity: the per-model `Train.scala`/`Test.scala`/`Utils.scala`
scopt CLIs (models/lenet/Train.scala, models/resnet/Train.scala, ...).
One CLI covers the zoo; flags mirror the reference's option names
(-f dataFolder, -b batchSize, --learningRate, --maxEpoch, --checkpoint).

    python -m bigdl_tpu.models.train --model lenet -f /data/mnist -b 128 \
        --maxEpoch 5 --checkpoint /tmp/ck --mesh data=8
    python -m bigdl_tpu.models.train --model resnet20-cifar -f /data/cifar \
        --synthetic  # no dataset on disk: synthetic stand-in
"""

from __future__ import annotations

import argparse
import logging


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="lenet",
                    help="lenet | resnet20-cifar | resnet50 | resnet18 | "
                         "inception-v1 | vgg16 | alexnet | "
                         "textclassifier | ncf | bilstm | transformer")
    ap.add_argument("-f", "--dataFolder", default=None)
    ap.add_argument("-b", "--batchSize", type=int, default=128)
    ap.add_argument("--learningRate", type=float, default=0.01)
    ap.add_argument("--maxEpoch", type=int, default=5)
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adam"])
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--weightDecay", type=float, default=0.0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--summary", default=None, help="TensorBoard log dir")
    ap.add_argument("--mesh", default=None, help="e.g. data=8")
    ap.add_argument("--synthetic", action="store_true",
                    help="use synthetic data (no dataset folder needed)")
    ap.add_argument("--records", default=None, metavar="DIR|GLOB",
                    help="train from disk-resident BDLS record shards "
                         "through the native dataplane (any vision "
                         "model; see bigdl_tpu.dataset.records)")
    ap.add_argument("--recordsMean", default="127.5",
                    help="comma per-channel mean for --records")
    ap.add_argument("--recordsStd", default="127.5",
                    help="comma per-channel std for --records")
    ap.add_argument("--recordsAug", default="",
                    help="comma subset of: hflip,pad<N> (e.g. hflip,pad4)")
    ap.add_argument("--moeExperts", type=int, default=0,
                    help="transformer only: Switch/GShard-MoE FFN with "
                         "this many experts (0 = dense)")
    ap.add_argument("--moeTopK", type=int, default=1, choices=[1, 2])
    ap.add_argument("--moeRouting", default="top_k",
                    choices=["top_k", "expert_choice"])
    ap.add_argument("--tfrecords", default=None, metavar="DIR|GLOB",
                    help="train a vision model from TFRecord shards of "
                         "tf.train.Examples (image/shape/label layout; "
                         "see bigdl_tpu.dataset.tfrecord)")
    ap.add_argument("--precision", default=None,
                    choices=["bf16", "mixed", "fp32"],
                    help="bf16 → mixed-precision training")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    import jax

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.optim import (
        Adam, Optimizer, SGD, Top1Accuracy, Trigger,
    )
    from bigdl_tpu.utils.engine import setup_compile_cache
    from bigdl_tpu.visualization import TrainSummary, ValidationSummary

    setup_compile_cache()

    # ---- data + model
    if args.model == "lenet":
        from bigdl_tpu.dataset.mnist import load_mnist, synthetic_mnist
        from bigdl_tpu.models import lenet

        model = lenet.build(10)
        if args.synthetic or not args.dataFolder:
            train, val = synthetic_mnist(4096), synthetic_mnist(512, seed=9)
        else:
            train = load_mnist(args.dataFolder, train=True)
            val = load_mnist(args.dataFolder, train=False)
    elif args.model == "resnet20-cifar":
        from bigdl_tpu.dataset.cifar import load_cifar10, synthetic_cifar10
        from bigdl_tpu.models import resnet

        model = resnet.build_cifar(20, 10)
        if args.synthetic or not args.dataFolder:
            train, val = synthetic_cifar10(2048), synthetic_cifar10(256, seed=9)
        else:
            train = load_cifar10(args.dataFolder, train=True)
            val = load_cifar10(args.dataFolder, train=False)
    elif args.model in ("textclassifier", "ncf", "bilstm"):
        import numpy as np
        from bigdl_tpu.dataset import Sample

        rng = np.random.RandomState(0)
        n = args.batchSize * 4
        if args.model == "textclassifier":
            from bigdl_tpu.models import textclassifier

            model = textclassifier.build(class_num=4, vocab_size=200,
                                         sequence_len=200)
            ys = rng.randint(0, 4, n)
            train = [Sample(rng.randint(y * 50, y * 50 + 50,
                                        200).astype(np.int32), int(y))
                     for y in ys]
        elif args.model == "ncf":
            from bigdl_tpu.models import ncf

            model = ncf.build(64, 128, class_num=5)
            train = [Sample(np.asarray(
                [rng.randint(64), rng.randint(128)], np.int32),
                np.int32(rng.randint(5))) for _ in range(n)]
        else:  # bilstm sentiment
            from bigdl_tpu.models import rnn

            model = rnn.bilstm_sentiment(100, embed_dim=32, hidden_size=32)
            ys = rng.randint(0, 2, n)
            train = [Sample(rng.randint(y * 40, y * 40 + 40,
                                        24).astype(np.int32), int(y))
                     for y in ys]
        val = train[:args.batchSize]
    elif args.model == "transformer":
        from bigdl_tpu.dataset.text import synthetic_next_token
        from bigdl_tpu.models.transformer import (TransformerConfig,
                                                  TransformerLM)

        seq = 32
        model = TransformerLM(TransformerConfig(
            vocab_size=64, dim=128, num_heads=4, num_layers=2,
            max_len=seq, moe_experts=args.moeExperts,
            moe_top_k=args.moeTopK, moe_routing=args.moeRouting))
        train = synthetic_next_token(args.batchSize * 4, 64, seq)
        val = train[:args.batchSize]
    else:
        from bigdl_tpu.models.perf import _build_model
        import numpy as np
        from bigdl_tpu.dataset import Sample

        if args.dataFolder:
            raise SystemExit(
                f"--dataFolder is not supported for model {args.model!r} "
                "(only lenet / resnet20-cifar have dataset loaders); drop "
                "-f to train on synthetic data")
        model, shape, classes = _build_model(args.model, 1000)
        if args.records or args.tfrecords:
            train, val = [], []  # disk shards replace the synthetic pool
        else:
            rng = np.random.RandomState(0)
            train = [Sample(rng.rand(*shape).astype(np.float32),
                            np.int32(rng.randint(classes)))
                     for _ in range(args.batchSize * 4)]
            val = train[:args.batchSize]

    model.build(jax.random.PRNGKey(42))

    method = (SGD(learningrate=args.learningRate, momentum=args.momentum,
                  dampening=0.0, weightdecay=args.weightDecay)
              if args.optimizer == "sgd" else Adam(args.learningRate))

    if args.model == "transformer":
        # LM path: the fused chunked criterion keeps the (B, S, V)
        # log-prob tensor off the training step entirely
        criterion = nn.ChunkedSoftmaxCE()
        from bigdl_tpu.optim import Loss
        val_methods = [Loss(criterion)]
    else:
        criterion = nn.ClassNLLCriterion()
        val_methods = [Top1Accuracy()]

    if args.records and args.tfrecords:
        raise SystemExit("--records and --tfrecords are exclusive")
    if (args.records or args.tfrecords) and args.model in (
            "transformer", "textclassifier", "ncf", "bilstm"):
        raise SystemExit(
            f"record shards hold images; model {args.model!r} takes "
            "token inputs (use a vision model)")
    if args.tfrecords:
        import numpy as np

        from bigdl_tpu.dataset import Sample, TFRecordDataSet
        from bigdl_tpu.dataset.tfrecord import default_image_parser

        if args.recordsAug:
            raise SystemExit(
                "--recordsAug applies to --records (native-plane "
                "augmentation); TFRecord training is unaugmented")
        mean = np.asarray([float(v) for v in args.recordsMean.split(",")],
                          np.float32)
        std = np.asarray([float(v) for v in args.recordsStd.split(",")],
                         np.float32)

        def parser(example):
            s = default_image_parser(example)
            return Sample((s.feature - mean) / std, s.label)

        train_ds = TFRecordDataSet(args.tfrecords, parser=parser)
        logging.getLogger("bigdl_tpu").info(
            "tfrecords: %d samples from %d shards (mean=%s std=%s)",
            train_ds.size(), len(train_ds.paths), mean, std)
        val_ds = train_ds
    elif args.records:
        # disk-resident path: BDLS shards → native mmap prefetcher
        # (reference: the Spark-executor-fed ImageNet pipeline,
        # SURVEY.md §2.4/§7; dataset/records.py)
        from bigdl_tpu.dataset import RecordFileDataSet, resolve_shards
        from bigdl_tpu.dataset.records import read_header

        _, _, _, chans = read_header(resolve_shards(args.records)[0])

        def _per_channel(spec):
            vals = [float(v) for v in spec.split(",")]
            return vals * chans if len(vals) == 1 else vals

        pad, hflip = 0, False
        for tok in filter(None, args.recordsAug.split(",")):
            if tok == "hflip":
                hflip = True
            elif tok.startswith("pad"):
                pad = int(tok[3:])
            else:
                raise SystemExit(f"unknown --recordsAug token {tok!r}")
        train_ds = RecordFileDataSet(
            args.records, args.batchSize, mean=_per_channel(args.recordsMean),
            std=_per_channel(args.recordsStd), pad=pad, hflip=hflip)
        logging.getLogger("bigdl_tpu").info(
            "records: %d samples %s from %d shards (native=%s)",
            train_ds.size(), train_ds.shape, len(train_ds.paths),
            train_ds.native)
        val_ds = train_ds  # eval iterates the shards once, unaugmented
    else:
        train_ds = DataSet.array(train)
        val_ds = DataSet.array(val)

    opt = (Optimizer(model, train_ds, criterion,
                     batch_size=args.batchSize)
           .set_optim_method(method)
           .set_end_when(Trigger.max_epoch(args.maxEpoch))
           .set_validation(Trigger.every_epoch(), val_ds,
                           val_methods, args.batchSize))
    if args.checkpoint:
        opt.set_checkpoint(args.checkpoint, Trigger.every_epoch())
        if args.resume:
            opt.resume_from_checkpoint()
    if args.summary:
        opt.set_train_summary(TrainSummary(args.summary, args.model))
        opt.set_validation_summary(ValidationSummary(args.summary, args.model))
    if args.precision and args.precision != "fp32":
        opt.set_precision("bf16")
    if args.mesh:
        from bigdl_tpu.parallel import make_mesh, parse_axes

        opt.set_mesh(make_mesh(parse_axes(args.mesh)))

    opt.optimize()


if __name__ == "__main__":
    main()
