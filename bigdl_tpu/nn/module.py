"""Module contract — TPU-native equivalent of BigDL's ``AbstractModule``.

Reference: scala/dllib/.../nn/abstractnn/AbstractModule.scala. The reference
contract is ``forward = updateOutput``, ``backward = updateGradInput +
accGradParameters`` with hand-written gradients per layer, and
``parameters()`` exposing flattened weight/grad views used by
AllReduceParameter.

The TPU-native design (SURVEY.md §7.1):

- Every module owns **hyperparameters** (static python) plus nested
  **param** and **state** dicts of ``jax.Array`` leaves (state = running
  stats etc., the non-trainable collection).
- The compute path is the *pure* method ``apply(params, states, input,
  training=..., rng=...) -> (output, new_states)`` — closed over only
  static config, so it jits/grads/vmaps/shard_maps cleanly.
- The BigDL-facing stateful facade (``forward``/``backward``/
  ``parameters``/``zero_grad_parameters``) is preserved for API parity and
  layer-by-layer numerics tests; ``backward`` is derived from ``jax.vjp``
  of ``apply`` rather than hand-written updateGradInput code.

Activities may be single arrays or :class:`bigdl_tpu.utils.table.Table`
(multi-input/output), both of which are pytrees.
"""

from __future__ import annotations

import zlib
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.utils.table import Table

_instance_counters: Dict[str, int] = {}


def _flat_keys(tree, prefix=""):
    """Yield (dotted_path, leaf) for a nested-dict pytree."""
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _flat_keys(v, f"{prefix}{k}.")
    else:
        yield prefix.rstrip("."), tree


def _auto_name(cls_name: str) -> str:
    n = _instance_counters.get(cls_name, 0)
    _instance_counters[cls_name] = n + 1
    return f"{cls_name}{n}"


class _GlobalRng:
    """Deterministic global parameter-init RNG (ref: RandomGenerator).

    The key is built on first use, not here: creating a PRNGKey
    initialises the JAX backend, and the two module-level streams below
    would make ``import bigdl_tpu.nn`` take the chip — a parent that
    only imports the package must stay off it so its children can have
    it (one process per chip)."""

    def __init__(self, seed: int = 0):
        self._seed = seed
        self._key = None

    def set_seed(self, seed: int):
        self._seed = seed
        self._key = None

    def next_key(self):
        if self._key is None:
            self._key = jax.random.PRNGKey(self._seed)
        self._key, sub = jax.random.split(self._key)
        return sub


RNG = _GlobalRng()           # parameter initialisation stream
FORWARD_RNG = _GlobalRng(1)  # stateful-facade forward stream (dropout etc.)


def set_seed(seed: int):
    """Set the global parameter-initialisation seed."""
    RNG.set_seed(seed)
    FORWARD_RNG.set_seed(seed + 1)


def fold_name(rng, name: str):
    """Derive a child rng deterministically from a scope name."""
    return jax.random.fold_in(rng, zlib.crc32(name.encode()) & 0x7FFFFFFF)


class Module:
    """Base module (ref: AbstractModule[A, B, T])."""

    def __init__(self, name: Optional[str] = None):
        # bypass __setattr__ routing while bootstrapping
        object.__setattr__(self, "_params", OrderedDict())
        object.__setattr__(self, "_states", OrderedDict())
        object.__setattr__(self, "_modules", OrderedDict())
        object.__setattr__(self, "_grads", None)
        self.name = name or _auto_name(type(self).__name__)
        self._train = True
        self.output = None
        self.grad_input = None

    # -- registration -------------------------------------------------------
    def __setattr__(self, key, value):
        if isinstance(value, Module):
            self._modules[key] = value
        object.__setattr__(self, key, value)

    def add_param(self, name: str, value):
        self._params[name] = jnp.asarray(value)

    def add_state(self, name: str, value):
        self._states[name] = jnp.asarray(value)

    # -- tree collection ----------------------------------------------------
    def parameters_dict(self) -> Dict[str, Any]:
        d = dict(self._params)
        for name, mod in self._modules.items():
            sub = mod.parameters_dict()
            if sub:
                d[name] = sub
        return d

    def states_dict(self) -> Dict[str, Any]:
        d = dict(self._states)
        for name, mod in self._modules.items():
            sub = mod.states_dict()
            if sub:
                d[name] = sub
        return d

    def load_parameters_dict(self, params: Dict[str, Any]):
        for k in self._params:
            if k in params:
                self._params[k] = jnp.asarray(params[k])
        for name, mod in self._modules.items():
            if name in params:
                mod.load_parameters_dict(params[name])
        return self

    def load_states_dict(self, states: Dict[str, Any]):
        for k in self._states:
            if k in states:
                self._states[k] = jnp.asarray(states[k])
        for name, mod in self._modules.items():
            if name in states:
                mod.load_states_dict(states[name])
        return self

    def modules(self):
        """Depth-first iteration over submodules, self first."""
        yield self
        for mod in self._modules.values():
            yield from mod.modules()

    def named_modules(self, prefix: str = ""):
        yield prefix or self.name, self
        for name, mod in self._modules.items():
            yield from mod.named_modules(f"{prefix}.{name}" if prefix else name)

    # -- pure compute path ---------------------------------------------------
    def apply(self, params, states, x, *, training: bool = False, rng=None):
        """Pure forward. Returns ``(output, new_states)``.

        Subclasses implement :meth:`_apply`; returning a bare output means
        "states unchanged".
        """
        out = self._apply(params, states, x, training=training, rng=rng)
        if isinstance(out, tuple) and len(out) == 2 and isinstance(out[1], dict):
            return out
        return out, states

    def _apply(self, params, states, x, *, training, rng):
        raise NotImplementedError(
            f"{type(self).__name__} must implement _apply"
        )

    def sub_apply(self, name: str, params, states, x, *, training, rng):
        """Invoke child ``name`` with its param/state sub-scopes."""
        mod = self._modules[name]
        sub_rng = None if rng is None else fold_name(rng, name)
        y, new_sub = mod.apply(
            params.get(name, {}), states.get(name, {}), x,
            training=training, rng=sub_rng,
        )
        return y, new_sub

    def child_runner(self, params, states, *, training, rng):
        """``(run, finalize)`` for composite ``_apply`` bodies: ``run(name,
        x)`` dispatches to child ``name`` collecting its state updates;
        ``finalize()`` returns ``states`` merged with every update."""
        new_states: Dict[str, Any] = {}

        def run(name, x):
            y, sub = self.sub_apply(name, params, states, x,
                                    training=training, rng=rng)
            if sub:
                new_states[name] = sub
            return y

        def finalize():
            merged = dict(states)
            merged.update(new_states)
            return merged

        return run, finalize

    # -- stateful facade (BigDL parity) --------------------------------------
    def forward(self, x):
        x = _to_jax(x)
        # dedicated facade stream, NOT the param-init RNG — keeps set_seed
        # reproducibility of layer construction independent of forward calls
        rng = FORWARD_RNG.next_key() if self._train else None
        object.__setattr__(self, "_last_rng", rng)
        y, new_states = self.apply(
            self.parameters_dict(), self.states_dict(), x,
            training=self._train, rng=rng,
        )
        self.load_states_dict(new_states)
        self.output = y
        return y

    __call__ = forward

    def backward(self, x, grad_output):
        """updateGradInput + accGradParameters via jax.vjp (ref semantics).

        Reuses the rng drawn by the preceding ``forward`` so stochastic
        layers (Dropout) see the same mask in both passes, matching the
        reference's stored-mask updateGradInput.
        """
        x = _to_jax(x)
        grad_output = _to_jax(grad_output)
        states = self.states_dict()
        rng = getattr(self, "_last_rng", None)
        if rng is None:
            rng = jax.random.PRNGKey(0)

        def f(p, xi):
            return self.apply(p, states, xi, training=self._train, rng=rng)[0]

        _, vjp = jax.vjp(f, self.parameters_dict(), x)
        gp, gi = vjp(grad_output)
        if self._grads is None:
            object.__setattr__(self, "_grads", gp)
        else:
            object.__setattr__(
                self, "_grads",
                jax.tree_util.tree_map(jnp.add, self._grads, gp),
            )
        self.grad_input = gi
        return gi

    def update_output(self, x):
        return self.forward(x)

    def update_grad_input(self, x, grad_output):
        return self.backward(x, grad_output)

    def zero_grad_parameters(self):
        object.__setattr__(
            self, "_grads",
            jax.tree_util.tree_map(jnp.zeros_like, self.parameters_dict()),
        )
        return self

    def parameters(self) -> Tuple[list, list]:
        """(weights, gradWeights) flat lists (ref: parameters())."""
        leaves = jax.tree_util.tree_leaves(self.parameters_dict())
        if self._grads is None:
            grads = [jnp.zeros_like(w) for w in leaves]
        else:
            grads = jax.tree_util.tree_leaves(self._grads)
        return leaves, grads

    def get_weights(self):
        return jax.tree_util.tree_map(np.asarray, self.parameters_dict())

    def set_weights(self, weights):
        return self.load_parameters_dict(weights)

    # -- modes ---------------------------------------------------------------
    def training(self):
        for m in self.modules():
            m._train = True
        return self

    def evaluate(self):
        for m in self.modules():
            m._train = False
        return self

    def is_training(self) -> bool:
        return self._train

    # -- misc parity ----------------------------------------------------------
    def set_name(self, name: str):
        self.name = name
        return self

    def get_name(self) -> str:
        return self.name

    def reset(self):
        """Re-initialise parameters (ref: reset()). Default: no-op."""
        for m in self._modules.values():
            m.reset()
        return self

    def clear_state(self):
        self.output = None
        self.grad_input = None
        for m in self._modules.values():
            m.clear_state()
        return self

    def n_parameters(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.parameters_dict()))

    # -- persistence (ref: ModuleSerializer protobuf; here: pickle) ----------
    def __getstate__(self):
        state = dict(self.__dict__)
        state["_params"] = OrderedDict(
            (k, np.asarray(v)) for k, v in self._params.items())
        state["_states"] = OrderedDict(
            (k, np.asarray(v)) for k, v in self._states.items())
        state["_grads"] = None
        state["output"] = None
        state["grad_input"] = None
        state.pop("_jit_fwd", None)   # compiled-function cache is not picklable
        state.pop("_last_rng", None)
        return state

    def __setstate__(self, state):
        for k, v in state.items():
            object.__setattr__(self, k, v)
        self._params = OrderedDict(
            (k, jnp.asarray(v)) for k, v in state["_params"].items())
        self._states = OrderedDict(
            (k, jnp.asarray(v)) for k, v in state["_states"].items())

    def save_weights(self, path: str):
        """Persist params+states in the stable versioned checkpoint format
        (manifest.json + arrays.safetensors — no code execution on load);
        reload into user-constructed code with :meth:`load_weights`."""
        from bigdl_tpu.utils.checkpoint import save_checkpoint
        save_checkpoint(path,
                        {"params": self.parameters_dict(),
                         "states": self.states_dict()},
                        metadata={"class": type(self).__name__})
        return self

    def load_weights(self, path: str, strict: bool = True) -> "Module":
        """Load params/states saved by :meth:`save_weights`. With
        ``strict`` (default) the checkpoint must structurally match this
        module — a mismatched checkpoint raising beats silently keeping
        random init weights."""
        from bigdl_tpu.utils.checkpoint import load_checkpoint
        tree, meta = load_checkpoint(path)
        if strict:
            saved_cls = meta.get("class")
            if saved_cls is not None and saved_cls != type(self).__name__:
                raise ValueError(
                    f"checkpoint was saved from {saved_cls}, loading into "
                    f"{type(self).__name__} (pass strict=False to force)")
            want = {p for p, _ in _flat_keys(self.parameters_dict())}
            have = {p for p, _ in _flat_keys(tree["params"])}
            if want != have:
                raise ValueError(
                    f"checkpoint params do not match module: missing="
                    f"{sorted(want - have)[:5]} unexpected="
                    f"{sorted(have - want)[:5]} (pass strict=False)")
        self.load_parameters_dict(tree["params"])
        if tree.get("states"):
            self.load_states_dict(tree["states"])
        return self

    def save_module(self, path: str, overwrite: bool = True):
        """Persist the module as a checkpoint DIRECTORY: the stable
        manifest + safetensors weights (readable by any version via
        ``load_checkpoint``) plus a ``structure.pkl`` sidecar holding the
        weight-stripped module object for same-version reconstruction.
        (ref role: ModuleSerializer protobuf persistence.)"""
        import os
        import pickle
        if not overwrite and os.path.exists(path):
            raise IOError(f"{path} exists and overwrite=False")
        params, states = self.parameters_dict(), self.states_dict()
        try:
            # strip weights from the pickled structure: arrays live only
            # in the safetensors file
            self.load_parameters_dict(jax.tree_util.tree_map(
                lambda a: np.zeros((0,), np.asarray(a).dtype), params))
            self.load_states_dict(jax.tree_util.tree_map(
                lambda a: np.zeros((0,), np.asarray(a).dtype), states))
            structure = pickle.dumps(self)
        finally:
            self.load_parameters_dict(params)
            self.load_states_dict(states)
        # ONE atomic save: weights, manifest and the structure sidecar
        # all publish together (a crash mid-save can't leave a dir that
        # load_weights accepts but load_module chokes on)
        from bigdl_tpu.utils.checkpoint import save_checkpoint
        save_checkpoint(path,
                        {"params": params, "states": states},
                        metadata={"class": type(self).__name__},
                        extra_files={"structure.pkl": structure})
        return self

    @staticmethod
    def load_module(path: str) -> "Module":
        import os
        import pickle
        if os.path.isdir(path):
            with open(os.path.join(path, "structure.pkl"), "rb") as f:
                module = pickle.load(f)
            return module.load_weights(path)
        # legacy round-1 single-file pickle checkpoints
        with open(path, "rb") as f:
            return pickle.load(f)

    def __repr__(self):
        lines = [f"{type(self).__name__}({self.name})"]
        for name, mod in self._modules.items():
            sub = repr(mod).splitlines()
            lines.append(f"  ({name}): {sub[0]}")
            lines.extend("  " + s for s in sub[1:])
        return "\n".join(lines)


def _to_jax(x):
    """Coerce user input (numpy / Tensor facade / Table / pytree) to jax."""
    from bigdl_tpu.tensor import Tensor

    def conv(v):
        if isinstance(v, Tensor):
            return v.data
        if isinstance(v, np.ndarray):
            return jnp.asarray(v)
        return v

    if isinstance(x, (Table, list, tuple, dict)):
        return jax.tree_util.tree_map(conv, x)
    return conv(x)


class TensorModule(Module):
    """Module whose input/output are single tensors (ref: TensorModule)."""


class Criterion:
    """Loss contract (ref: AbstractCriterion) — forward(input,target)->scalar.

    Pure path: ``apply_loss(input, target) -> scalar jnp array``. The
    stateful facade mirrors the reference (``forward``/``backward``), with
    ``backward`` = grad of the loss wrt input via jax.
    """

    def __init__(self, size_average: bool = True):
        self.size_average = size_average
        self.output = None
        self.grad_input = None

    def apply_loss(self, x, target):
        raise NotImplementedError

    def forward(self, x, target):
        self.output = self.apply_loss(_to_jax(x), _to_jax(target))
        return float(self.output)

    __call__ = forward

    def backward(self, x, target):
        x = _to_jax(x)
        target = _to_jax(target)
        self.grad_input = jax.grad(lambda xi: self.apply_loss(xi, target))(x)
        return self.grad_input
