"""KerasEstimator / KerasModel.

Reference: ``horovod/spark/keras/estimator.py:92`` + ``remote.py`` —
Spark ML Estimator that trains a keras model under Horovod with
``DistributedOptimizer`` + broadcast/metric-average callbacks and
checkpoints through the ``Store``.

Same TPU-native shape as the torch estimator: the training loop runs
on this framework's rank launcher; the DataFrame leg is a pyspark-gated
adapter over :meth:`KerasEstimator.fit_arrays`.
"""

import pickle

import numpy as np

from ..common.params import EstimatorParams
from ..common.store import Store
from ..common.util import (
    batch_to_xy, extract_x, extract_xy, require_pyspark,
    split_validation, stage_dataframe_to_store, synced_step_count,
)


class KerasEstimator(EstimatorParams):
    """``model`` is a compiled-or-not keras model; ``optimizer`` a
    keras optimizer (re-created per rank from its config); ``loss`` a
    keras loss (name or callable)."""

    def fit(self, df, params=None):
        """Spark entry: executors write the DataFrame as Parquet into
        the store (no driver materialization), ranks stream shards
        (reference keras/remote.py make_batch_reader flow)."""
        require_pyspark()
        if self.store is None:
            # small-data fallback; warns — driver materialization
            from ..common.util import warn_driver_materialization

            warn_driver_materialization(df, "KerasEstimator.fit(df)")
            x, y = extract_xy(df.toPandas(), self.feature_cols,
                              self.label_cols)
            return self.fit_arrays(x, y)
        train_path, val_path = stage_dataframe_to_store(
            df, self.store, self.feature_cols, self.label_cols,
            sample_weight_col=self.sample_weight_col,
            validation=self.validation)
        return self.fit_on_parquet(train_path, val_path)

    def fit_on_parquet(self, train_path, val_path=None):
        """Stream a Parquet dataset per rank (Petastorm role —
        reference store.py:38-540) into ``model.fit`` via a generator
        dataset."""
        from ... import run as hvd_run
        from ... import keras as hvd_keras
        from ..common.reader import make_batch_reader

        est = self
        model_blob = _serialize_keras(self.model)
        opt_conf = _optimizer_config(self.optimizer)
        store = self.store
        run_id = self.run_id or "run"
        feature_cols = list(self.feature_cols)
        label_cols = list(self.label_cols)
        weight_col = self.sample_weight_col
        schema = feature_cols + label_cols + \
            ([weight_col] if weight_col else [])

        def to_fit_tuple(batch):
            if est.transformation_fn is not None:
                batch = est.transformation_fn(batch)
            xy = batch_to_xy(batch, feature_cols, label_cols)
            if weight_col:
                # keras consumes (x, y, sample_weight) triples natively
                return xy + (np.asarray(batch[weight_col],
                                        np.float32),)
            return xy

        def train_fn():
            import tensorflow as tf

            rank, size = hvd_keras.rank(), hvd_keras.size()
            model = _deserialize_keras(model_blob)
            opt = tf.keras.optimizers.get(
                {"class_name": opt_conf[0], "config": opt_conf[1]})
            opt = hvd_keras.DistributedOptimizer(opt)
            model.compile(optimizer=opt, loss=est.loss,
                          metrics=list(est.metrics), run_eagerly=True)
            cb = [hvd_keras.callbacks.BroadcastGlobalVariablesCallback(0),
                  hvd_keras.callbacks.MetricAverageCallback()]
            cb += list(est.callbacks)

            def cycling(epoch):
                sub = 0
                while True:
                    reader = make_batch_reader(
                        train_path, schema_fields=schema,
                        batch_size=est.batch_size, cur_shard=rank,
                        shard_count=size,
                        shuffle_row_groups=est.shuffle,
                        seed=est.epoch_seed(epoch * 1000 + sub))
                    for b in reader:
                        yield to_fit_tuple(b)
                    sub += 1

            hist_all = {}
            for epoch in range(est.epochs):
                if est.train_steps_per_epoch:
                    steps = est.train_steps_per_epoch
                else:
                    # equalized step count: shards can differ by a row
                    # group; a lone extra gradient allreduce would
                    # deadlock (reference keras/remote.py
                    # steps_per_epoch)
                    probe = make_batch_reader(
                        train_path, schema_fields=schema,
                        batch_size=est.batch_size, cur_shard=rank,
                        shard_count=size)
                    n_local = -(-probe.num_rows // est.batch_size)
                    steps = synced_step_count(n_local,
                                              name=f"ksteps.{epoch}")
                fit_kw = {}
                if val_path is not None:
                    vreader = make_batch_reader(
                        val_path, schema_fields=schema,
                        batch_size=est.effective_val_batch_size,
                        cur_shard=rank, shard_count=size)
                    vsteps = est.validation_steps_per_epoch or \
                        max(-(-vreader.num_rows
                              // est.effective_val_batch_size), 1)
                    fit_kw = {"validation_data":
                              (to_fit_tuple(b) for b in vreader),
                              "validation_steps": vsteps}
                hist = model.fit(cycling(epoch), epochs=1,
                                 steps_per_epoch=steps,
                                 callbacks=cb,
                                 verbose=est.verbose if rank == 0
                                 else 0, **fit_kw)
                for k, vs in hist.history.items():
                    hist_all.setdefault(k, []).extend(
                        float(v) for v in vs)
            if rank == 0:
                blob = pickle.dumps(
                    {"json": pickle.loads(model_blob)["json"],
                     "weights": model.get_weights()},
                    protocol=pickle.HIGHEST_PROTOCOL)
                if store is not None:
                    store.save_checkpoint(run_id, blob)
                return blob, hist_all
            return None

        results = hvd_run(train_fn, np=self.num_proc)
        blob, history = next(r for r in results if r is not None)
        return KerasModel(model=_deserialize_keras(blob),
                          history=history,
                          feature_cols=self.feature_cols,
                          label_cols=self.label_cols,
                          run_id=run_id, store=store)

    def fit_arrays(self, x, y, x_val=None, y_val=None):
        from ... import run as hvd_run
        from ... import keras as hvd_keras

        x = np.asarray(x)
        y = np.asarray(y)
        x, y, x_val, y_val = split_validation(x, y, x_val, y_val,
                                              self.validation)

        est = self
        model_blob = _serialize_keras(self.model)
        opt_conf = _optimizer_config(self.optimizer)
        store = self.store
        run_id = self.run_id or "run"

        def train_fn():
            import tensorflow as tf

            rank, size = hvd_keras.rank(), hvd_keras.size()
            model = _deserialize_keras(model_blob)
            opt = tf.keras.optimizers.get(
                {"class_name": opt_conf[0], "config": opt_conf[1]})
            opt = hvd_keras.DistributedOptimizer(opt)
            # eager train step: this frontend stages gradients through
            # host numpy (the TF binding is eager-first), which a
            # compiled tf.function train_step cannot do
            model.compile(optimizer=opt, loss=est.loss,
                          metrics=list(est.metrics), run_eagerly=True)
            cb = [hvd_keras.callbacks.BroadcastGlobalVariablesCallback(0),
                  hvd_keras.callbacks.MetricAverageCallback()]
            cb += list(est.callbacks)
            val = (x_val, y_val) if x_val is not None else None
            hist = model.fit(x[rank::size], y[rank::size],
                             batch_size=est.batch_size,
                             epochs=est.epochs,
                             validation_data=val,
                             callbacks=cb,
                             verbose=est.verbose if rank == 0 else 0)
            if rank == 0:
                # pair the pre-compile architecture json with the
                # trained weights: the compiled model's config embeds
                # the dynamic Distributed* optimizer class, which
                # cannot deserialize (reference keras/util.py saves
                # with include_optimizer=False for the same reason)
                blob = pickle.dumps(
                    {"json": pickle.loads(model_blob)["json"],
                     "weights": model.get_weights()},
                    protocol=pickle.HIGHEST_PROTOCOL)
                if store is not None:
                    store.save_checkpoint(run_id, blob)
                return blob, {k: [float(v) for v in vs]
                              for k, vs in hist.history.items()}
            return None

        results = hvd_run(train_fn, np=self.num_proc)
        blob, history = next(r for r in results if r is not None)
        return KerasModel(model=_deserialize_keras(blob),
                          history=history,
                          feature_cols=self.feature_cols,
                          label_cols=self.label_cols,
                          run_id=run_id, store=store)


class KerasModel:
    def __init__(self, model=None, history=None, feature_cols=None,
                 label_cols=None, run_id=None, store=None):
        self.model = model
        self.history = history or {}
        self.feature_cols = feature_cols
        self.label_cols = label_cols
        self.run_id = run_id
        self.store = store

    def getModel(self):
        return self.model

    def transform_arrays(self, x):
        return np.asarray(self.model.predict(np.asarray(x), verbose=0))

    def make_predict_fn(self, batch_size=1024, output_col="prediction"):
        """Partition-level inference closure (reference keras
        estimator ``_transform`` predict-per-partition); the model is
        re-deserialized per executor partition."""
        from ..common.util import make_predict_partition_fn

        def predict_batch(model, x):
            return np.asarray(model.predict(x, verbose=0))

        return make_predict_partition_fn(
            _serialize_keras(self.model), _deserialize_keras,
            predict_batch, self.feature_cols, batch_size=batch_size,
            output_col=output_col)

    def transform(self, df):
        """Adds a prediction column on the EXECUTORS partition by
        partition (never ``toPandas``)."""
        from ..common.util import transform_dataframe

        return transform_dataframe(df, self.make_predict_fn())

    @classmethod
    def load(cls, store: Store, run_id: str, **kwargs):
        blob = store.load_checkpoint(run_id)
        if blob is None:
            raise FileNotFoundError(f"no checkpoint for run {run_id}")
        return cls(model=_deserialize_keras(blob), run_id=run_id,
                   store=store, **kwargs)


def _serialize_keras(model) -> bytes:
    """Architecture + weights, no tf SavedModel dir (reference
    keras/util.py serialize_model uses h5 bytes the same way)."""
    payload = {"json": model.to_json(),
               "weights": model.get_weights()}
    return pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)


def _deserialize_keras(blob: bytes):
    import tensorflow as tf
    payload = pickle.loads(blob)
    model = tf.keras.models.model_from_json(payload["json"])
    model.set_weights(payload["weights"])
    return model


def _optimizer_config(opt):
    import tensorflow as tf
    if isinstance(opt, str):
        opt = tf.keras.optimizers.get(opt)
    return opt.__class__.__name__, opt.get_config()


# -- MLlib-style persistence surface (reference spark/keras/estimator.py
#    KerasEstimatorParams{Writable,Readable,Writer,Reader}) -----------------

from ..common.serialization import (  # noqa: E402
    HorovodParamsReader, HorovodParamsWriter, ParamsReadable,
    ParamsWritable,
)


class KerasEstimatorParamsWriter(HorovodParamsWriter):
    pass


class KerasEstimatorParamsReader(HorovodParamsReader):
    pass


class KerasEstimatorParamsWritable(ParamsWritable):
    pass


class KerasEstimatorParamsReadable(ParamsReadable):
    pass


KerasEstimator.write = ParamsWritable.write
KerasEstimator.save = ParamsWritable.save
KerasEstimator.read = classmethod(ParamsReadable.read.__func__)
KerasEstimator.load = classmethod(ParamsReadable.load.__func__)
