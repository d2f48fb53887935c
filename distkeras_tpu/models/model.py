"""Model wrapper: the user-facing handle trainers consume.

Replaces the reference's Keras model objects (shipped pickled to Spark
executors; reference ``distkeras/utils.py:serialize_keras_model`` /
``deserialize_keras_model``).  A ``Model`` binds a layer graph + input shape
and exposes pure ``init``/``apply``; trainers thread the ``variables`` pytree
through jit-compiled steps.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax

from .layers import Layer, Sequential, layer_from_config


class Model:
    def __init__(self, layer: Layer, input_shape: Optional[Sequence[int]] = None,
                 name: str = "model"):
        if input_shape is None and isinstance(layer, Sequential):
            input_shape = layer.input_shape
        if input_shape is None:
            raise ValueError("Model needs an input_shape")
        self.layer = layer
        self.input_shape = tuple(input_shape)
        self.name = name
        self.output_shape = layer.out_shape(self.input_shape)
        #: trained variables pytree, attached by trainers after ``train()``
        #: (the reference returns a weight-laden Keras model the same way)
        self.variables: Optional[dict] = None

    # -- functional API -----------------------------------------------------
    def init(self, rng=0) -> dict:
        if isinstance(rng, int):
            rng = jax.random.PRNGKey(rng)
        params, state, _ = self.layer.init(rng, self.input_shape)
        return {"params": params, "state": state}

    def apply(self, variables: dict, x, *, train: bool = False, rng=None):
        return self.layer.apply(variables["params"], variables["state"], x,
                                train=train, rng=rng)

    def iter_layers(self):
        """All layers in the model, depth-first (``Layer.iter_layers``)."""
        return self.layer.iter_layers()

    def predict_fn(self):
        """Pure inference function suitable for jit: (variables, x) -> y."""
        def fn(variables, x):
            y, _ = self.apply(variables, x, train=False)
            return y
        return fn

    def decode_logits(self, out):
        """The logits a user decodes from, of what ``apply`` or
        ``predict_fn`` gave: ``out`` itself for a model with one answer; for
        one that may answer after any pass (``layers.ExitHeads``) each
        token's logits at the first pass whose exit chances add up to its
        threshold."""
        return self.layer.decode_logits(out)

    # -- serde --------------------------------------------------------------
    def config(self) -> dict:
        return {"name": self.name, "input_shape": list(self.input_shape),
                "layer": self.layer.config()}

    @classmethod
    def from_config(cls, cfg: dict) -> "Model":
        return cls(layer_from_config(cfg["layer"]),
                   input_shape=cfg["input_shape"], name=cfg.get("name", "model"))

    def __repr__(self):
        return (f"Model({self.name!r}, in={self.input_shape}, "
                f"out={self.output_shape}, layer={self.layer!r})")


def num_params(variables: dict) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(variables["params"]))
