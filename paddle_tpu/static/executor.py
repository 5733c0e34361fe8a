"""Static Executor — the InterpreterCore analog.

Ref: paddle/fluid/framework/new_executor/interpreter_core.* +
python/paddle/base/executor.py (upstream layout, unverified — mount empty).
Paddle builds an instruction list with dependency analysis and async streams;
here the Program replays into ONE pure jax function (op fns from the
registry), jit-compiled per feed signature and cached — XLA does the
scheduling/fusion the InterpreterCore hand-rolls. Programs carrying a
minimize hook (optimizer.minimize in static mode) compile the full train
step: forward + jax.grad + functional optimizer update, params donated.
"""
from __future__ import annotations

import weakref
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..ops.registry import get_op
from .program import Program, Variable, default_main_program

__all__ = ["Executor", "Scope", "global_scope"]


class Scope:
    """Name -> value store (ref: paddle/fluid/framework/scope.*); thin here
    because persistables live on the Program's ref table."""

    def __init__(self):
        self._vars = {}

    def var(self, name):
        return self._vars.setdefault(name, None)

    def find_var(self, name):
        return self._vars.get(name)

    def set(self, name, value):
        self._vars[name] = value


_GLOBAL_SCOPE = Scope()


def global_scope() -> Scope:
    return _GLOBAL_SCOPE


def _replay(program: Program, env: Dict[str, jax.Array]):
    """Execute the op list over `env` (name -> array), mutating env."""
    for op in program.global_block().ops:
        fn = op.fn if getattr(op, "fn", None) is not None else \
            get_op(op.type).fn

        def build(template):
            out = []
            for kind, payload in template:
                if kind == "var":
                    out.append(env[op.input_names[payload]])
                elif kind == "list":
                    out.append([env[op.input_names[p]] if k == "var" else p
                                for k, p in payload])
                else:
                    out.append(payload)
            return out

        result = fn(*build(op.arg_template), **op.attrs)
        outs = (list(result) if isinstance(result, (tuple, list))
                else [result])
        for name, val in zip(op.output_names, outs):
            env[name] = val
    return env


class Executor:
    """paddle.static.Executor over a compiled-callable cache."""

    def __init__(self, place=None):
        self.place = place
        self._cache = {}

    def run(self, program: Optional[Program] = None, feed: Optional[Dict] = None,
            fetch_list: Optional[Sequence] = None, scope=None,
            return_numpy: bool = True):
        program = program or default_main_program()
        if hasattr(program, "program") and not hasattr(program, "refs"):
            program = program.program     # CompiledProgram unwrap
        feed = feed or {}
        fetch_list = list(fetch_list or [])

        from .io import LoadedInferenceModel

        if isinstance(program, LoadedInferenceModel):
            outs = program.run(feed)
            if fetch_list:
                by_name = dict(zip(program.fetch_names, outs))
                outs = [by_name[f.name if hasattr(f, "name") else str(f)]
                        for f in fetch_list]
            if return_numpy:
                return [np.asarray(o) for o in outs]
            return [Tensor(o) for o in outs]

        if not program.global_block().ops:
            return []  # startup program: params already initialized eagerly

        fetch_names = [f.name if isinstance(f, Variable) else str(f)
                       for f in fetch_list]
        feed_arrays = {}
        for k, v in feed.items():
            if isinstance(v, Tensor):
                feed_arrays[k] = v._data
            else:
                feed_arrays[k] = jnp.asarray(np.asarray(v))

        param_names = sorted(program.refs.keys())
        param_arrays = {n: program.refs[n]._data for n in param_names}

        # fleet static path: a minimize-carrying Program with a hybrid dist
        # context (pp_degree>1) runs through the pipeline engine
        dist_ctx = getattr(program, "_dist_context", None)
        if (program._minimize_hooks and dist_ctx
                and dist_ctx.get("mesh") is not None):
            strategy = dist_ctx.get("strategy")
            hc = strategy.hybrid_configs if strategy is not None else {}
            if int(hc.get("pp_degree", 1)) > 1:
                return self._run_hybrid(program, feed_arrays, fetch_names,
                                        return_numpy, dist_ctx)

        sig = (id(program),
               tuple(sorted((k, v.shape, str(v.dtype))
                            for k, v in feed_arrays.items())),
               tuple(fetch_names),
               # train-ness and mesh identity: minimize()/fleet context may
               # attach AFTER a forward-only run cached an eval callable
               bool(program._minimize_hooks),
               id(dist_ctx["mesh"]) if dist_ctx else None)
        compiled = self._cache.get(sig)
        if compiled is None:
            compiled = self._compile(
                program, fetch_names, bool(program._minimize_hooks),
                mesh=dist_ctx.get("mesh") if dist_ctx else None)
            self._cache[sig] = compiled

        if program._minimize_hooks:
            for opt, _, _ in program._minimize_hooks:
                opt._step_count += 1
            opt = program._minimize_hooks[0][0]
            lr = jnp.asarray(opt.get_lr(), dtype=jnp.float32)
            t = jnp.asarray(opt._step_count, dtype=jnp.int32)
            opt_state = self._opt_state(program, param_arrays)
            fetches, new_params, new_opt_state = compiled(
                feed_arrays, param_arrays, opt_state, lr, t)
            self._opt_states[program] = new_opt_state
            for n in param_names:
                program.refs[n]._data = new_params[n]
        else:
            fetches = compiled(feed_arrays, param_arrays)

        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return [Tensor(f) for f in fetches]

    # by the program itself, not its id: a dead program's id is reused
    _opt_states = weakref.WeakKeyDictionary()

    def _opt_state(self, program, param_arrays):
        st = self._opt_states.get(program)
        if st is None:
            opt = program._minimize_hooks[0][0]
            names = self._trainable_names(program)
            st = opt.functional_state(
                {n: param_arrays[n] for n in names})
            self._opt_states[program] = st
        return st

    @staticmethod
    def _is_trainable(program, name):
        from ..core.tensor import Parameter

        t = program.refs.get(name)
        return isinstance(t, Parameter) and not t.stop_gradient

    @staticmethod
    def _trainable_names(program):
        """Trainable persistables, honoring minimize's parameters (restrict)
        and no_grad_set (exclude; accepts names or tensors) — a frozen param
        silently updating is the bug class this guards (paddle contract)."""
        names = [n for n in sorted(program.refs)
                 if Executor._is_trainable(program, n)]
        if not program._minimize_hooks:
            return names
        _, _, (params_filter, no_grad_set) = program._minimize_hooks[0]
        if params_filter:
            allowed = {id(p) for p in params_filter}
            allowed_names = {getattr(p, "name", None) for p in params_filter}
            names = [n for n in names
                     if id(program.refs[n]) in allowed
                     or n in allowed_names]
        if no_grad_set:
            excl_ids = {id(x) for x in no_grad_set
                        if not isinstance(x, str)}
            excl_names = {x for x in no_grad_set if isinstance(x, str)}
            excl_names |= {getattr(x, "name", None) for x in no_grad_set
                           if not isinstance(x, str)}
            names = [n for n in names
                     if n not in excl_names
                     and id(program.refs[n]) not in excl_ids
                     and getattr(program.refs[n], "name", None)
                     not in excl_names]
        return names

    def _run_hybrid(self, program, feed_arrays, fetch_names, return_numpy,
                    dist_ctx):
        """Static TP+PP train step via the fleet meta-optimizer engine."""
        from .fleet_pass import StaticHybridEngine

        if not hasattr(self, "_hybrid_engines"):
            self._hybrid_engines = {}

        opt, loss_var, _ = program._minimize_hooks[0]
        if fetch_names and fetch_names != [loss_var.name]:
            raise NotImplementedError(
                "the static hybrid (pp) path currently fetches only the "
                f"loss {loss_var.name!r}, got {fetch_names}")
        engine = self._hybrid_engines.get(id(program))
        if engine is None:
            engine = StaticHybridEngine(
                program, dist_ctx["mesh"], dist_ctx.get("strategy"),
                getattr(opt, "_inner_opt", opt), loss_var.name,
                self._trainable_names(program))
            self._hybrid_engines[id(program)] = engine
            dist_ctx["engine"] = engine   # observability (tests, tooling)
        loss = engine.train_step(feed_arrays)
        outs = [loss] if fetch_names else []
        if return_numpy:
            return [np.asarray(o) for o in outs]
        return [Tensor(o) for o in outs]

    def _compile(self, program: Program, fetch_names: List[str],
                 train: bool, mesh=None):
        # GSPMD shardings for the fleet TP/DP static path (pp collapsed):
        # params from dist_spec marks, feeds batch-sharded, one jit over the
        # whole mesh — XLA inserts the Megatron collectives
        param_in_sh = feed_in_sh = None
        if mesh is not None:
            from .fleet_pass import data_sharding, program_param_shardings

            param_in_sh = program_param_shardings(program, mesh)
            feed_in_sh = data_sharding(mesh)

        if not train:
            def fwd(feed_arrays, param_arrays):
                env = dict(param_arrays)
                env.update(feed_arrays)
                _replay(program, env)
                return [env[n] for n in fetch_names]

            if mesh is not None:
                # prefix pytree: one sharding broadcast over the feed dict
                return jax.jit(fwd, in_shardings=(feed_in_sh, param_in_sh))
            return jax.jit(fwd)

        opt, loss_var, _ = program._minimize_hooks[0]
        loss_name = loss_var.name

        def step(feed_arrays, param_arrays, opt_state, lr, t):
            trainable_names = self._trainable_names(program)
            frozen = {n: a for n, a in param_arrays.items()
                      if n not in trainable_names}

            def loss_of(trainable):
                env = dict(frozen)
                env.update(trainable)
                env.update(feed_arrays)
                _replay(program, env)
                return jnp.sum(env[loss_name]).astype(jnp.float32), env

            (_, env), grads = jax.value_and_grad(
                loss_of, has_aux=True)(
                {n: param_arrays[n] for n in trainable_names})
            new_trainable, new_state = opt.functional_step(
                {n: param_arrays[n] for n in trainable_names}, grads,
                opt_state, lr, t)
            new_params = dict(param_arrays)
            new_params.update(new_trainable)
            return ([env[n] for n in fetch_names], new_params, new_state)

        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(mesh, P())
            opt_sh = {n: param_in_sh[n]
                      for n in self._trainable_names(program)}
            return jax.jit(step,
                           in_shardings=(feed_in_sh, param_in_sh, opt_sh,
                                         repl, repl),
                           donate_argnums=(1, 2))
        return jax.jit(step, donate_argnums=(1, 2))
