"""Data normalizers.

Parity surface: nd4j ``NormalizerStandardize`` / ``NormalizerMinMaxScaler`` /
``ImagePreProcessingScaler`` used with reference iterators
(``iterator.setPreProcessor(normalizer)``) and persisted inside model zips
(ModelSerializer normalizer slot).
"""

from __future__ import annotations

import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet


class Normalizer:
    #: When True, iterators attached via ``set_pre_processor`` hand batches
    #: through RAW and the network containers apply the transform ON DEVICE
    #: after the host->device copy (``as_device_transform``). With byte
    #: image data this cuts the wire bytes 4x — the host->device link
    #: (PCIe) is routinely the bottleneck of plain fit(iterator) training,
    #: not the math. Off by default: reference semantics apply the
    #: processor iterator-side.
    device_side = False

    def fit(self, data):
        """Accepts a DataSet or an iterator of DataSets."""
        if isinstance(data, DataSet):
            self._fit_arrays([data.features])
            return self
        if hasattr(data, "reset"):
            data.reset()
        self._fit_arrays([d.features for d in data])
        return self

    def as_device_transform(self):
        """A jax-traceable features transform equivalent to
        ``transform_features`` (None = not supported device-side)."""
        return None

    def _fit_arrays(self, arrays):
        raise NotImplementedError

    def transform(self, ds: DataSet) -> DataSet:
        ds.features = self.transform_features(ds.features)
        return ds

    def transform_features(self, f):
        raise NotImplementedError

    def revert_features(self, f):
        raise NotImplementedError

    def pre_process(self, ds: DataSet):
        return self.transform(ds)

    def to_dict(self):
        raise NotImplementedError

    @staticmethod
    def from_dict(d):
        cls = {c.__name__: c for c in
               (NormalizerStandardize, NormalizerMinMaxScaler,
                ImagePreProcessingScaler)}[d["@type"]]
        return cls._from_dict(d)


class NormalizerStandardize(Normalizer):
    """Zero-mean unit-variance per feature."""

    def __init__(self, device_side=False):
        self.device_side = device_side
        self.mean = None
        self.std = None

    def _fit_arrays(self, arrays):
        flat = np.concatenate([a.reshape(a.shape[0], -1) for a in arrays])
        self.mean = flat.mean(axis=0)
        self.std = flat.std(axis=0) + 1e-8

    def transform_features(self, f):
        shape = f.shape
        out = (f.reshape(shape[0], -1) - self.mean) / self.std
        return out.reshape(shape).astype(f.dtype)

    def revert_features(self, f):
        shape = f.shape
        out = f.reshape(shape[0], -1) * self.std + self.mean
        return out.reshape(shape).astype(f.dtype)

    def to_dict(self):
        return {"@type": "NormalizerStandardize",
                "mean": self.mean.tolist(), "std": self.std.tolist()}

    @classmethod
    def _from_dict(cls, d):
        n = cls()
        n.mean = np.asarray(d["mean"])
        n.std = np.asarray(d["std"])
        return n

    def as_device_transform(self):
        import jax.numpy as jnp
        mean = jnp.asarray(np.asarray(self.mean), jnp.float32)
        std = jnp.asarray(np.asarray(self.std), jnp.float32)

        def fn(f):
            # accepts (B, ...) or stacked (S, B, ...) blocks: flatten to the
            # per-example feature width the stats were fit on
            shape = f.shape
            out = (f.reshape(-1, mean.shape[0]).astype(jnp.float32)
                   - mean) / std
            return out.reshape(shape)
        return fn


class NormalizerMinMaxScaler(Normalizer):
    def __init__(self, min_range=0.0, max_range=1.0, device_side=False):
        self.device_side = device_side
        self.min_range = min_range
        self.max_range = max_range
        self.data_min = None
        self.data_max = None

    def _fit_arrays(self, arrays):
        flat = np.concatenate([a.reshape(a.shape[0], -1) for a in arrays])
        self.data_min = flat.min(axis=0)
        self.data_max = flat.max(axis=0)

    def transform_features(self, f):
        shape = f.shape
        span = np.maximum(self.data_max - self.data_min, 1e-8)
        out = (f.reshape(shape[0], -1) - self.data_min) / span
        out = out * (self.max_range - self.min_range) + self.min_range
        return out.reshape(shape).astype(f.dtype)

    def revert_features(self, f):
        shape = f.shape
        span = np.maximum(self.data_max - self.data_min, 1e-8)
        out = (f.reshape(shape[0], -1) - self.min_range) / (self.max_range - self.min_range)
        out = out * span + self.data_min
        return out.reshape(shape).astype(f.dtype)

    def to_dict(self):
        return {"@type": "NormalizerMinMaxScaler",
                "min_range": self.min_range, "max_range": self.max_range,
                "data_min": self.data_min.tolist(),
                "data_max": self.data_max.tolist()}

    @classmethod
    def _from_dict(cls, d):
        n = cls(d["min_range"], d["max_range"])
        n.data_min = np.asarray(d["data_min"])
        n.data_max = np.asarray(d["data_max"])
        return n

    def as_device_transform(self):
        import jax.numpy as jnp
        span = jnp.asarray(np.maximum(np.asarray(self.data_max)
                                      - np.asarray(self.data_min), 1e-8),
                           jnp.float32)
        dmin = jnp.asarray(np.asarray(self.data_min), jnp.float32)
        lo, hi = float(self.min_range), float(self.max_range)

        def fn(f):
            # accepts (B, ...) or stacked (S, B, ...) blocks
            shape = f.shape
            out = (f.reshape(-1, dmin.shape[0]).astype(jnp.float32)
                   - dmin) / span
            return (out * (hi - lo) + lo).reshape(shape)
        return fn


class ImagePreProcessingScaler(Normalizer):
    """Scales pixel values [0, max_pixel] → [min, max] (parity:
    ImagePreProcessingScaler, default /255). With ``device_side=True`` and
    uint8 features, fit(iterator) ships 1 byte/pixel over the host->device
    link and scales on chip."""

    def __init__(self, min_range=0.0, max_range=1.0, max_pixel=255.0,
                 device_side=False):
        self.device_side = device_side
        self.min_range = min_range
        self.max_range = max_range
        self.max_pixel = max_pixel

    def _fit_arrays(self, arrays):
        pass  # stateless

    def transform_features(self, f):
        out = f / self.max_pixel * (self.max_range - self.min_range) + self.min_range
        return out.astype(np.float32)

    def revert_features(self, f):
        return ((f - self.min_range) / (self.max_range - self.min_range)
                * self.max_pixel).astype(np.float32)

    def to_dict(self):
        return {"@type": "ImagePreProcessingScaler",
                "min_range": self.min_range, "max_range": self.max_range,
                "max_pixel": self.max_pixel}

    @classmethod
    def _from_dict(cls, d):
        return cls(d["min_range"], d["max_range"], d["max_pixel"])

    def as_device_transform(self):
        import jax.numpy as jnp
        lo, hi, mp = (float(self.min_range), float(self.max_range),
                      float(self.max_pixel))

        def fn(f):
            return f.astype(jnp.float32) / mp * (hi - lo) + lo
        return fn
