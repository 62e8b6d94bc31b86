"""The model template a `vit_b16` cell uploads through `Client.create_model`:
ViT (models/vit.py) trained by `DataParallelTrainer`, the learning rate
proposed by the advisor, everything else fixed. It is `chip_smoke.py`'s
SmokeViT with two changes: the weights come from the seed by the benchmark's
own recipe (so that the plain reference can make the same ones without
importing the program), and no mid-trial checkpoint is written (a 1 GB file
an epoch; the machine keeps every block once written).

The `# @cell` lines are set by the harness from the configuration and the
traffic file; as they stand they are the tiny size the CPU rehearsal runs.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax

from rafiki_tpu.models import vit
from rafiki_tpu.models.transformer import TransformerConfig
from rafiki_tpu.sdk import (BaseModel, DataParallelTrainer, FixedKnob,
                            FloatKnob, cached_trainer,
                            classification_accuracy, dataset_utils,
                            softmax_classifier_loss, tunable_optimizer)

SEED = 0  # @cell
IMAGE = 32  # @cell
PATCH = 4  # @cell
CHANNELS = 3  # @cell
DIM = 64  # @cell
DEPTH = 2  # @cell
HEADS = 4  # @cell
CLASSES = 10  # @cell
BATCH = 8  # @cell
EPOCHS = 2  # @cell
LR_MIN = 1e-4  # @cell
LR_MAX = 1e-3  # @cell
FAULT = ""  # @cell

CFG = vit.ViTConfig(
    image_size=IMAGE, patch_size=PATCH, channels=CHANNELS,
    num_classes=CLASSES,
    encoder=TransformerConfig(dim=DIM, depth=DEPTH, heads=HEADS))


def make_params(key):
    """The benchmark's weight recipe, laid out as models/vit.py's tree:
    leaf i is `normal(fold_in(key, i)) * std` in the order below."""
    d, h, n, f = DIM, HEADS, DEPTH, 4 * DIM
    dh, s = d // h, (IMAGE // PATCH) ** 2
    xav = lambda a, b: math.sqrt(2.0 / (a + b))
    spec = [
        ((PATCH, PATCH, CHANNELS, d), math.sqrt(2.0 / (PATCH * PATCH * CHANNELS))),
        ((d,), 0.0), ((1, s, d), 0.02),
        ((n, d), 1.0), ((n, d), 0.0),
        ((n, d, h, dh), xav(d, d)), ((n, d, h, dh), xav(d, d)),
        ((n, d, h, dh), xav(d, d)), ((n, h, dh, d), xav(d, d)), ((n, d), 0.0),
        ((n, d), 1.0), ((n, d), 0.0),
        ((n, d, f), xav(d, f)), ((n, f), 0.0),
        ((n, f, d), xav(f, d)), ((n, d), 0.0),
        ((d,), 1.0), ((d,), 0.0),
        ((d, CLASSES), xav(d, CLASSES)), ((CLASSES,), 0.0),
    ]
    leaves = []
    for i, (shape, std) in enumerate(spec):
        if std == 1.0:
            leaves.append(jnp.ones(shape, jnp.float32))
        elif std == 0.0:
            leaves.append(jnp.zeros(shape, jnp.float32))
        else:
            leaves.append(jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32) * std)
    (pk, pb, pos, g1, b1, wq, wk, wv, wo, bo, g2, b2, k1, c1, k2, c2,
     gf, bf, hk, hb) = leaves
    return {
        "patch": {"kernel": pk, "bias": pb},
        "pos": pos,
        "blocks": {
            "ln1": {"scale": g1, "bias": b1},
            "attn": {"wq": wq, "wk": wk, "wv": wv, "wo": wo, "bo": bo},
            "ln2": {"scale": g2, "bias": b2},
            "mlp": {"w1": {"kernel": k1, "bias": c1},
                    "w2": {"kernel": k2, "bias": c2}},
        },
        "ln_f": {"scale": gf, "bias": bf},
        "head": {"kernel": hk, "bias": hb},
    }


def _apply(params, x):
    return vit.apply(params, x, CFG)


def _loss():
    base = softmax_classifier_loss(_apply)
    if FAULT != "half_batch":
        return base

    def half(params, batch, rng):  # tests only: half of each batch left
        x, y = batch               # out, the mean taken over the rest
        return base(params, (x[:x.shape[0] // 2], y[:y.shape[0] // 2]), rng)

    return half


class BenchViT(BaseModel):
    dependencies = {"jax": None, "optax": None}

    @staticmethod
    def get_knob_config():
        return {
            "learning_rate": FloatKnob(LR_MIN, LR_MAX, is_exp=True),
            "batch_size": FixedKnob(BATCH),
            "epochs": FixedKnob(EPOCHS),
        }

    def __init__(self, **knobs):
        super().__init__(**knobs)
        self._knobs = knobs
        self._params = None

    def _trainer(self):
        return cached_trainer(("BenchViT", CFG, FAULT), lambda: DataParallelTrainer(
            _loss(),
            tunable_optimizer(optax.adamw, learning_rate=1e-3),
            predict_fn=lambda p, x: jax.nn.softmax(_apply(p, x), axis=-1)))

    def train(self, dataset_uri):
        x, y = dataset_utils.load_image_arrays(dataset_uri)
        trainer = self._trainer()
        params, opt_state = trainer.init(
            jax.jit(make_params), seed=SEED,
            hyperparams={"learning_rate": self._knobs["learning_rate"]})
        if FAULT == "frozen":       # tests only: the step leaves its state
            self._params = params   # as it was
            self.logger.log(loss=0.0, epoch=0.0, epoch_time=0.0)
            return
        self._params, _ = trainer.fit(
            params, opt_state, (x, y), epochs=self._knobs["epochs"],
            batch_size=self._knobs["batch_size"], seed=SEED,
            log=self.logger.log)

    def evaluate(self, dataset_uri):
        x, y = dataset_utils.load_image_arrays(dataset_uri)
        return classification_accuracy(self._trainer(), self._params, x, y)

    def predict(self, queries):
        probs = self._trainer().predict_batched(
            self._params, np.asarray(queries, np.float32))
        return [p.tolist() for p in probs]

    def dump_parameters(self):
        return {"params": jax.tree.map(np.asarray, self._params)}

    def load_parameters(self, blob):
        self._params = self._trainer().device_put_params(blob["params"])
