"""The comparison that decides `correct`, for cells that train.

Both sides are reduced to the same readings. Of the first three steps (the
proof steps):

  loss    the loss of each step
  grad    per leaf, the norm of the first gradient as the optimizer got it,
          worked out from its state after one step (the configuration's
          files say how: `first_gradient_sq`)
  update  per leaf, the norm of the parameters' change after the three steps
  state   per leaf, the norm of the change of the running statistics over
          the first step

and, where the window drives a program of n steps a call, of that program's
first call, which follows the proof steps (steps 4 to 3+n, the warm-up):

  loop_loss    the loss of each of its steps
  loop_update  per leaf, the norm of the parameters' change from the first
               weights to the end of the call
  loop_move    per leaf, the norm of their change over the call alone

They are compared number by number: a loss by its widest relative gap, the
rest by the worst leaf, as the gap between the two norms (not the norm of the
difference) over the reference's norm of that leaf or of the median leaf,
whichever is larger. A gap between norms cannot see rounding noise, which has
no sign: the int8 control reads on them what the bfloat16 program reads. So
two more numbers are read. `grad_diff`: per leaf, the norm of the difference
of the first gradient's magnitudes, element by element, over the same scale.
`dead_grad_noise`: over the leaves whose reference gradient is under a
thousandth of the median leaf's (a convolution's bias before a batch norm:
nought but for rounding), the largest norm the program shows there, over the
median leaf's. Those leaves are left out of `update` and of the loop's
numbers. The reference follows the call of n steps too. Both zoo
configurations move every weight by about its own spread in a step, so two
sound sides part from about the sixth step on (their losses by a factor of
three to five more each step), and what a later step of that call can be
held to is scale and not value: `loop_move_gap`, the gap between the two
sides' norms of a leaf's move over the call (a state left unchanged reads 1),
and `loop_loss_repeats`, how many of the call's losses repeat the one before
(a step that does not update repeats its loss: the batch of a call is the
same rows, rotated). Where a configuration's loss has come to rest by the
call's end (the zoo ResNet50 learns its 512 images by heart within twenty
steps and both sides end on the regulariser's floor), the last loss is value
again: `loop_last_loss_gap`. `loop_loss_gap` and `loop_update_norm_gap` are
read and reported as well. Imports nothing of the program.
"""
from __future__ import annotations

import statistics
import sys
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import refmath

PROOF_STEPS = 3
DEAD_GRADIENT = 1e-3          # of the median leaf's gradient norm
REPEATED_LOSS = 1e-4          # relative: a loss this near the one before repeats it


def _host(tree) -> Dict[str, float]:
    return {k: float(v) for k, v in jax.device_get(tree).items()}


@jax.jit
def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def _norms_from_squares(grad_sq) -> Dict[str, float]:
    return {k: max(v, 0.0) ** 0.5
            for k, v in _host(refmath.leaf_sums(grad_sq)).items()}


class Readings:
    """What one side showed over the proof steps and the first call of the
    window's own program."""

    def __init__(self):
        self.loss = []
        self.grad: Dict[str, float] = {}
        self.grad_sq1 = None    # the first gradient's squares, leaf by leaf
        self.update: Dict[str, float] = {}
        self.state: Dict[str, float] = {}
        self.loop_loss = []
        self.loop_update: Dict[str, float] = {}
        self.loop_move: Dict[str, float] = {}


def _follow(ref, cfg, params0, step: Callable, loop_steps: int) -> Readings:
    """Drive `step(params, state) -> (params, state, loss, updater's state of
    the replica that is read)` from `params0` through the proof steps and
    `loop_steps` more."""
    params = _copy(params0)
    state0 = ref.init_state(cfg)
    state = _copy(state0)
    out = Readings()
    for n in range(PROOF_STEPS):
        params, state, loss, opt = step(params, state)
        out.loss.append(float(loss))
        if n == 0:
            out.grad_sq1 = ref.first_gradient_sq(cfg, opt)
            out.grad = _norms_from_squares(out.grad_sq1)
            if state0:
                out.state = _host(refmath.diff_norms(state, state0))
    out.update = _host(refmath.diff_norms(params, params0))
    if loop_steps:
        before = _copy(params)
        for _ in range(loop_steps):
            params, state, loss, _ = step(params, state)
            out.loop_loss.append(float(loss))
        out.loop_update = _host(refmath.diff_norms(params, params0))
        out.loop_move = _host(refmath.diff_norms(params, before))
    return out


def follow_reference(ref, cfg, params0, batch, mode: str = "f32",
                     rows: Optional[int] = None, loop_steps: int = 0) -> Readings:
    """Drive the plain reference through the proof steps from `params0` on
    `batch`, and through `loop_steps` more. `mode` "int8" is the control;
    `rows` keeps only the first rows of the batch (the fault "half of the
    batch left out")."""
    x, y = (jnp.asarray(a) for a in batch)
    if rows is not None:
        x, y = x[:rows], y[:rows]
    opt = [ref.init_opt(cfg, params0)]

    def step(params, state):
        params, opt[0], state, loss = ref.train_step(cfg, mode, params, opt[0],
                                                     state, x, y)
        return params, state, loss, opt[0]
    return _follow(ref, cfg, params0, step, loop_steps)


@jax.jit
def _encode(update, residual, threshold):
    """DL4J's threshold encoding (EncodingHandler): what of update + residual
    reaches the threshold is sent as +-threshold, the rest stays behind."""
    acc = jax.tree_util.tree_map(jnp.add, update, residual)
    msg = jax.tree_util.tree_map(
        lambda a: jnp.where(jnp.abs(a) >= threshold, jnp.sign(a) * threshold, 0.0),
        acc)
    return msg, jax.tree_util.tree_map(jnp.subtract, acc, msg)


@jax.jit
def _tree_mean(trees):
    return jax.tree_util.tree_map(lambda *a: sum(a) / len(a), *trees)


def follow_reference_replicated(ref, cfg, params0, batch, replicas: int,
                                threshold: float, mode: str = "f32",
                                exchange: bool = True,
                                rows: Optional[int] = None,
                                loop_steps: int = 0) -> Readings:
    """The plain reference of DL4J's ParallelWrapper with shared gradients:
    every replica takes its rows of the batch (in order), runs its own updater
    on its own gradient, threshold-encodes the update against its residual,
    and all subtract the SUM of the replicas' messages; running statistics
    are averaged. The readings are replica 0's, as the wrapper writes them
    back. `exchange=False` is the fault "the exchange between chips left
    out": replica 0 subtracts its own message alone. `rows` keeps only the
    first rows of every replica's share (the fault "half of the batch left
    out")."""
    # the whole batch on one device, whatever mesh the program kept it on
    x, y = (jax.device_put(jnp.asarray(a), jax.devices()[0]) for a in batch)
    share = x.shape[0] // replicas
    rows = share if rows is None else rows
    shards = [(x[r * share:r * share + rows], y[r * share:r * share + rows])
              for r in range(replicas)]
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params0)
    opts = [ref.init_opt(cfg, params0) for _ in range(replicas)]
    residuals = [zeros] * replicas

    def step(params, state):
        losses, msgs, states = [], [], []
        for r in range(replicas):
            loss, grads, new_state = ref.loss_and_grads(cfg, mode, params, state,
                                                        *shards[r])
            opts[r], update = ref.apply_updater(cfg, opts[r], grads)
            msg, residuals[r] = _encode(update, residuals[r], threshold)
            losses.append(float(loss))
            msgs.append(msg)
            states.append(new_state)
        total = jax.tree_util.tree_map(lambda *m: sum(m), *msgs) \
            if exchange else msgs[0]
        params = jax.tree_util.tree_map(jnp.subtract, params, total)
        state = _tree_mean(states) if state else state
        return params, state, sum(losses) / replicas, opts[0]
    return _follow(ref, cfg, params0, step, loop_steps)


class ProgramProbe:
    """Reads the same numbers off the program's net: between the proof steps
    that the driver makes through the window's own entry, and after the first
    call of the window's own program."""

    def __init__(self, adapter, cfg, params0, state0):
        self.adapter, self.cfg = adapter, cfg
        self.params0, self.state0 = params0, state0
        self.params3 = None
        self.readings = Readings()

    def after_step(self, net, step: int, loss, loop_follows: bool = False) -> None:
        """`step` counts from 1; `loss` is that step's loss. `loop_follows`:
        the driver will call `after_loop`, so the weights are kept for it."""
        self.readings.loss.append(float(loss))
        if step == 1:
            grad_sq = self.adapter.first_gradient_sq(net, self.cfg)
            self.readings.grad = _norms_from_squares(grad_sq)
            # kept on the host, off the chip's memory, until the reference runs
            self.readings.grad_sq1 = jax.device_get(grad_sq)
            if self.state0:
                self.readings.state = _host(refmath.diff_norms(
                    self.adapter.state_of(net), self.state0))
        if step == PROOF_STEPS:
            params = self.adapter.params_of(net)
            self.readings.update = _host(refmath.diff_norms(params, self.params0))
            self.state0 = None
            if loop_follows:
                # the program donates its parameters to its next call
                self.params3 = _copy(params)
            else:
                self.params0 = None

    def after_loop(self, net, losses) -> None:
        """After the first call of the window's own program of n steps, which
        followed the proof steps; `losses` are its steps' losses."""
        self.readings.loop_loss = [float(v) for v in losses]
        params = self.adapter.params_of(net)
        self.readings.loop_update = _host(refmath.diff_norms(params, self.params0))
        self.readings.loop_move = _host(refmath.diff_norms(params, self.params3))
        self.params0 = self.params3 = None           # free the copies


def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float], leaves):
    """{leaf: |prog - ref| / max(ref, median leaf's ref)}."""
    leaves = list(leaves)
    if not leaves:
        return {}
    floor = statistics.median(ref[k] for k in leaves)
    out = {}
    for k in leaves:
        gap = abs(prog.get(k, float("nan")) - ref[k]) / max(ref[k], floor, 1e-30)
        out[k] = gap if np.isfinite(gap) else float("inf")
    return out


@jax.jit
def _magnitude_diff_norms(sq_a, sq_b):
    """{leaf: || sqrt(a) - sqrt(b) ||}: the difference of the first gradient's
    magnitudes, element by element, from the two sides' squares."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        jnp.sqrt(jnp.maximum(sq_a[k].astype(jnp.float32), 0.0))
        - jnp.sqrt(jnp.maximum(sq_b[k].astype(jnp.float32), 0.0)))))
        for k in sq_b}


def _grad_diffs(prog: Readings, ref: Readings):
    """{leaf: ||  |g_prog| - |g_ref|  || / max(||g_ref||, median leaf's)}."""
    if prog.grad_sq1 is None or ref.grad_sq1 is None \
            or set(prog.grad_sq1) != set(ref.grad_sq1):
        return {}
    diff = _host(_magnitude_diff_norms(
        {k: jnp.asarray(v) for k, v in prog.grad_sq1.items()}, ref.grad_sq1))
    floor = statistics.median(ref.grad.values())
    return {k: v / max(ref.grad[k], floor, 1e-30) for k, v in diff.items()}


def _worst_and_median(out: Dict[str, dict], name: str, per_leaf) -> None:
    if not per_leaf:
        return
    leaf = max(per_leaf, key=per_leaf.get)
    out[name] = {"value": per_leaf[leaf], "leaf": leaf}
    out[name + "_median"] = {"value": statistics.median(per_leaf.values()),
                             "leaf": "median"}


def _loss_gaps(prog, ref):
    rel = [abs(p - r) / max(abs(r), 1e-30) if np.isfinite(p) else float("inf")
           for p, r in zip(prog, ref)]
    if len(prog) < len(ref) or not rel:      # a step with no loss
        rel.append(float("inf"))
    return rel


def gaps(prog: Readings, ref: Readings) -> Dict[str, dict]:
    """Every number the two sides allow: {name: {"value", "leaf"}}. Which of
    them a cell is judged by, and at what limit, is in the cell's file."""
    out = {}
    rel = _loss_gaps(prog.loss, ref.loss)
    out["loss1_gap"] = {"value": rel[0], "leaf": "step1"}
    out["loss_gap"] = {"value": max(rel),
                       "leaf": f"step{int(np.argmax(rel)) + 1}"}
    _worst_and_median(out, "grad_norm_gap",
                      _leaf_gaps(prog.grad, ref.grad, ref.grad))
    _worst_and_median(out, "grad_diff", _grad_diffs(prog, ref))
    median_grad = statistics.median(ref.grad.values())
    dead = [k for k in ref.grad if ref.grad[k] < DEAD_GRADIENT * median_grad]
    alive = [k for k in ref.update if k not in dead]
    if dead:
        noise = {k: prog.grad.get(k, float("inf")) / max(median_grad, 1e-30)
                 for k in dead}
        leaf = max(noise, key=noise.get)
        out["dead_grad_noise"] = {"value": noise[leaf], "leaf": leaf}
    _worst_and_median(out, "update_norm_gap",
                      _leaf_gaps(prog.update, ref.update, alive))
    _worst_and_median(out, "state_norm_gap",
                      _leaf_gaps(prog.state, ref.state, ref.state))
    if prog.loop_loss:
        steps = prog.loop_loss
        repeats = sum(1 for a, b in zip(steps, steps[1:])
                      if not abs(b - a) > REPEATED_LOSS * abs(a))
        out["loop_loss_repeats"] = {"value": float(repeats), "leaf": "steps"}
    if prog.loop_loss and ref.loop_loss:
        rel = _loss_gaps(prog.loop_loss, ref.loop_loss)
        out["loop_loss_gap"] = {
            "value": max(rel),
            "leaf": f"step{PROOF_STEPS + int(np.argmax(rel)) + 1}"}
        out["loop_last_loss_gap"] = {"value": rel[-1],
                                     "leaf": f"step{PROOF_STEPS + len(rel)}"}
        _worst_and_median(out, "loop_move_gap",
                          _leaf_gaps(prog.loop_move, ref.loop_move, alive))
        _worst_and_median(out, "loop_update_norm_gap",
                          _leaf_gaps(prog.loop_update, ref.loop_update, alive))
    return out


def judge(found: Dict[str, dict], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit", "leaf"}}). A number with no limit in
    the cell's file is reported and not judged; a limit with no number fails."""
    rows, ok = {}, True
    for name, limit in limits.items():
        got = found.get(name)
        value = None if got is None else got["value"]
        if value is None or not value <= limit:
            ok = False
        rows[name] = {"value": value, "limit": limit,
                      "leaf": None if got is None else got["leaf"]}
    for name, got in found.items():
        rows.setdefault(name, {"value": got["value"], "limit": None,
                               "leaf": got["leaf"]})
    return ok, rows


def report(rows: Dict[str, dict], file=sys.stderr) -> None:
    for name, r in rows.items():
        print(f"compared {name} = {r['value']!r} limit {r['limit']!r} "
              f"at {r['leaf']}", file=file, flush=True)
