"""JaxCnnPopulation — one AutoML trial trains a POPULATION of learning
rates simultaneously and reports the best member.

The product surface of the SDK's PopulationTrainer (SURVEY §7.3
"vmap-over-knobs": many trials per chip). Where JaxCnn spends one trial on
one learning rate, this template sweeps `population_size` log-spaced rates
between its `lr_min`/`lr_max` knobs inside ONE jitted program — the
population rides the vmap axis, so a chip that is underutilized by one
small CNN trains 8 for nearly the same wall time. The HPO layer then
searches over the *range* (and architecture knobs) while the population
brute-forces the rate inside it; each trial's score is best-of-K. The
reference's unit of work was one container per trial with a whole GPU
(reference admin/services_manager.py:117-126) — this lever does not exist
there.

Run `python examples/models/image_classification/JaxCnnPopulation.py` for
the local contract-conformance check.
"""

import os
import sys

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", "..")
)

import jax
import jax.numpy as jnp
import numpy as np
import optax

from rafiki_tpu.models import core
from rafiki_tpu.sdk import (
    BaseModel,
    CategoricalKnob,
    FixedKnob,
    FloatKnob,
    IntegerKnob,
    PopulationTrainer,
    cached_trainer,
    dataset_utils,
    softmax_classifier_loss,
    tunable_optimizer,
)


class JaxCnnPopulation(BaseModel):
    """Stem conv -> GAP -> dense softmax, trained as a lr population."""

    dependencies = {"jax": None, "optax": None}

    @staticmethod
    def get_knob_config():
        return {
            "epochs": IntegerKnob(1, 4),
            "base_channels": CategoricalKnob([16, 32]),
            "lr_min": FloatKnob(1e-4, 1e-3, is_exp=True),
            "lr_max": FloatKnob(1e-2, 1e-1, is_exp=True),
            "population_size": CategoricalKnob([4, 8]),
            "batch_size": CategoricalKnob([128, 256]),
            "image_size": FixedKnob(32),
        }

    def __init__(self, **knobs):
        super().__init__(**knobs)
        self._knobs = knobs
        self._params = None  # the winning member's params
        self._trainer = None
        self._best_lr = None

    # -- architecture ------------------------------------------------------

    def _apply(self, params, x):
        x = core.cast_for_compute(x)
        x = jax.nn.relu(core.conv2d(params["stem"], x))
        x = jax.nn.relu(core.conv2d(params["conv"], x, stride=2))
        x = jnp.mean(x, axis=(1, 2))  # GAP
        return core.dense(params["head"], x).astype(jnp.float32)

    def _make_init(self, cin, num_classes):
        base = self._knobs["base_channels"]

        def init_fn(rng):
            k1, k2, k3 = core.split_keys(rng, 3)
            return {
                "stem": core.conv2d_init(k1, 3, 3, cin, base),
                "conv": core.conv2d_init(k2, 3, 3, base, 2 * base),
                "head": core.dense_init(k3, 2 * base, num_classes),
            }

        return init_fn

    def _build_trainer(self):
        # cached by the static (program-shaping) knobs, like JaxCnn: trials
        # differing only in lr range / epochs reuse the compiled epoch scan
        key = ("JaxCnnPopulation", self._knobs["base_channels"],
               self._knobs["population_size"], self._knobs["image_size"])
        return cached_trainer(key, lambda: PopulationTrainer(
            softmax_classifier_loss(self._apply),
            tunable_optimizer(optax.adamw, learning_rate=1e-3),
            predict_fn=lambda p, x: jax.nn.softmax(self._apply(p, x), axis=-1),
        ))

    def _load(self, dataset_uri):
        size = self._knobs["image_size"]
        return dataset_utils.load_image_arrays(dataset_uri,
                                               image_size=(size, size))

    # -- BaseModel contract ------------------------------------------------

    def train(self, dataset_uri):
        x, y = self._load(dataset_uri)
        num_classes = int(y.max()) + 1
        k = int(self._knobs["population_size"])
        lo, hi = float(self._knobs["lr_min"]), float(self._knobs["lr_max"])
        lrs = np.geomspace(min(lo, hi), max(lo, hi), k).tolist()

        self._trainer = self._build_trainer()
        # winner selection needs held-out data: carve a val split off a
        # SHUFFLED view of the train set (dataset zips often arrive
        # class-ordered — an unshuffled tail would be a one-class val set
        # and make best-of-K selection meaningless). Deterministic
        # permutation so a resumed re-run sees the identical split, and
        # memoized on the (cached) trainer so successive trials pass the
        # SAME split arrays — that identity is what fit()'s cross-trial
        # device cache keys on.
        cached_split = getattr(self._trainer, "_split_cache", None)
        if (cached_split is not None
                and cached_split[0] is x and cached_split[1] is y):
            x_tr, y_tr, x_val, y_val = cached_split[2]
        else:
            perm = np.random.default_rng(0).permutation(len(x))
            xs, ys = x[perm], y[perm]
            n_val = max(len(xs) // 8, 1)
            x_tr, y_tr = xs[:-n_val], ys[:-n_val]
            x_val, y_val = xs[-n_val:], ys[-n_val:]
            # the keyed arrays are stored IN the entry: identity compare is
            # then safe against CPython id reuse after the dataset-cache
            # LRU evicts (a bare (id(x), id(y)) key could alias a new
            # dataset's arrays and silently reuse the old split)
            self._trainer._split_cache = (
                x, y, (x_tr, y_tr, x_val, y_val))
        params, opt = self._trainer.init(
            self._make_init(x.shape[-1], num_classes),
            {"learning_rate": lrs})
        self.logger.define_plot("Population loss", ["loss"], x_axis="epoch")
        params, _ = self._trainer.fit(
            params, opt, (x_tr, y_tr),
            epochs=self._knobs["epochs"],
            batch_size=self._knobs["batch_size"],
            log=self.logger.log,
            # mid-trial resume, same guarantee as the other templates
            checkpoint_path=self.checkpoint_path,
        )
        scores = self._trainer.member_scores(params, x_val, y_val)
        best = int(np.argmax(scores))
        self._best_lr = lrs[best]
        self._params = self._trainer.member_params(params, best)
        self.logger.log(
            f"population winner: member {best} (lr={lrs[best]:.2e})",
            best_member=float(best), best_val_accuracy=float(scores[best]))

    def evaluate(self, dataset_uri):
        x, y = self._load(dataset_uri)
        correct = 0
        for i in range(0, len(x), 256):
            probs = self._predict_chunk(x[i:i + 256])
            correct += int((np.argmax(probs, axis=-1) == y[i:i + 256]).sum())
        return correct / float(len(x))

    @property
    def _predict_jit(self):
        # one compiled call per chunk (eager op-by-op would pay a
        # dispatch, and on the chip a compile, per op)
        if getattr(self, "_predict_jit_fn", None) is None:
            self._predict_jit_fn = jax.jit(
                lambda p, xx: jax.nn.softmax(self._apply(p, xx), axis=-1))
        return self._predict_jit_fn

    def _predict_chunk(self, chunk):
        chunk = np.asarray(chunk, np.float32)
        n_real = len(chunk)
        pad = (-n_real) % 256 if n_real > 8 else (-n_real) % 8
        if pad:  # fixed pad ladder: two compiled shapes, no per-size churn
            chunk = np.concatenate(
                [chunk, np.zeros((pad,) + chunk.shape[1:], chunk.dtype)])
        return np.asarray(self._predict_jit(self._params, chunk))[:n_real]

    def predict(self, queries):
        x = np.asarray(queries, np.float32)
        out = []
        for i in range(0, len(x), 256):  # cap device batches
            out.extend(p.tolist() for p in self._predict_chunk(x[i:i + 256]))
        return out

    def dump_parameters(self):
        return {
            "params": jax.tree.map(np.asarray, self._params),
            "best_lr": float(self._best_lr or 0.0),
        }

    def load_parameters(self, params):
        self._best_lr = float(params.get("best_lr", 0.0))
        self._params = jax.tree.map(jnp.asarray, params["params"])


if __name__ == "__main__":
    from rafiki_tpu.sdk.model import test_model_class

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "..", "..", "datasets", "image_classification"))
    from load_cifar10 import synthetic_cifar  # type: ignore

    import tempfile

    from rafiki_tpu.sdk.dataset import write_numpy_dataset

    with tempfile.TemporaryDirectory() as d:
        (xtr, ytr), (xte, yte) = synthetic_cifar(512, 128)
        train_uri = write_numpy_dataset(
            xtr.astype(np.float32) / 255.0, ytr.astype(np.int32),
            os.path.join(d, "train.npz"))
        test_uri = write_numpy_dataset(
            xte.astype(np.float32) / 255.0, yte.astype(np.int32),
            os.path.join(d, "test.npz"))
        test_model_class(
            model_file_path=os.path.abspath(__file__),
            model_class="JaxCnnPopulation",
            task="IMAGE_CLASSIFICATION",
            dependencies={"jax": None, "optax": None},
            train_dataset_uri=train_uri,
            test_dataset_uri=test_uri,
            queries=(xtr[:2].astype(np.float32) / 255.0).tolist(),
        )
