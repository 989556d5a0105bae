"""BERT MLM+NSP pretraining through the program's normal entry points:
``models.BERTForPretrain`` under ``parallel.DataParallelTrainer(...,
fuse_step=True)`` on a ``{"dp": chips}`` mesh, bf16 AMP — built as
``chip_smoke._build_bert_trainer`` builds it (copied: the program may
change, the yardstick may not)."""
import numpy as np


def build_trainer(shapes, seed, devices):
    """(model, trainer, ctx).  ``shapes`` is the configuration file's
    content, or its ``rehearsal`` group."""
    import mxnet_tpu as mx
    from mxnet_tpu import models, parallel
    from mxnet_tpu.contrib import amp
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    prog, train = shapes["program"], shapes["training"]
    m = int(train["masked_positions"])
    np.random.seed(seed % 2 ** 32)  # the initializers draw from numpy
    mx.random.seed(seed % (2 ** 31 - 1))    # dropout keys
    ctx = mx.Context(devices[0].platform, 0)
    amp.init(target_dtype=train["amp_dtype"])

    class FullLenPretrain(HybridBlock):
        """Full-length sequences need no padding mask."""

        def __init__(self, mod, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.mod = mod

        def hybrid_forward(self, F, tokens, types, positions):
            return self.mod(tokens, types, None, positions)

    model = FullLenPretrain(models.BERTForPretrain(
        models.get_bert(
            prog["preset"], vocab_size=int(shapes["vocab_size"]),
            max_length=int(shapes["max_position_embeddings"]),
            dropout=float(shapes["hidden_dropout_prob"]),
            # the file's sizes are what runs, whatever the preset holds
            units=int(shapes["hidden_size"]),
            hidden_size=int(shapes["intermediate_size"]),
            num_layers=int(shapes["num_hidden_layers"]),
            num_heads=int(shapes["num_attention_heads"]),
            type_vocab_size=int(shapes["type_vocab_size"]))))
    model.initialize(mx.init.Xavier(), ctx=ctx)
    sce = SoftmaxCrossEntropyLoss()

    def loss_fn(outs, label):
        mlm_scores, nsp_scores = outs
        mlm = sce(mlm_scores, label[:, :m].reshape((-1,))).mean()
        return mlm + sce(nsp_scores, label[:, m]).mean()

    mesh = parallel.make_mesh({"dp": len(devices)}, devices=devices)
    dpt = parallel.DataParallelTrainer(
        model, loss_fn, train["optimizer"],
        {"learning_rate": float(train["learning_rate"])}, mesh=mesh,
        fuse_step=True)
    return model, dpt, ctx


def n_params(model, skip=None):
    return sum(int(np.prod(p.shape))
               for name, p in model.collect_params().items()
               if skip is None or skip not in name)


def flops_per_sample(model, shapes, seq, masked):
    """bench.py's v2 count (copied): forward + backward of one sample is
    6 x non-embedding parameters x seq, plus attention 12 x L x H x seq^2,
    plus the tied-weight MLM decode 6 x masked x H x vocab.  Embedding
    look-ups are gathers and recomputation is not counted."""
    layers, hidden = shapes["num_hidden_layers"], shapes["hidden_size"]
    return (6 * n_params(model, skip="embed") * seq
            + 12 * layers * hidden * seq * seq
            + 6 * masked * hidden * shapes["vocab_size"])
