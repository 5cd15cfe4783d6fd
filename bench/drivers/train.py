"""The training driver: the program's jitted train step fed from the corpus.

The mix file (``bench/traffic/<mix>.json``) states the sequence length and
the optimizer's settings; the cell file states the batch.  Set-up builds
one object -- the compiled ``training.train_step.make_train_step`` step
with its parameters and AdamW state, all made on the device from the
seed -- and drives it through its first ``CHECKED_STEPS`` steps in the
window's own loop (``Session.drive``): each step's batch is drawn on the
host (``corpus.train_batch`` for the seed and step) and uploaded, and up
to the cell's ``ahead`` steps are dispatched before the oldest one's loss
is fetched, so that the chip stays fed for some seconds while the host
stands still.  The window then runs that same object on, in the same
loop.  No step is dispatched after ``--seconds``; the window closes when
every step in flight then has returned its loss, and the rate is the
tokens of every step over that whole time.

The check follows the first ``CHECKED_STEPS`` steps in the float32
reference (the ``loss_and_grad`` of the configuration's model module,
``bench/models/``, then ``reference.adamw``), with the same batches, and
compares each step's loss, the norm of each leaf of the first gradient
as the optimizer received it (its first moment after one step, over
1 - b1), the norm of each leaf's change over the checked steps, and the
norm of each leaf's difference from the reference's first gradient.  A
number the cell's file gives no limit is not compared.
"""

from __future__ import annotations

import collections
import functools
import gc
import math
import time

import numpy as np

import corpus
import harness
import reference
import weights

CHECKED_STEPS = 3
# leaves whose reference gradient is below this share of the median
# leaf's are left out of the gradient and change comparisons: Adam moves
# them by round-off alone
TINY_GRAD = 1e-3


def leaf_norms(tree) -> dict:
    """{path: float32 norm} of every leaf, computed on the device."""
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, norms)}


def host_leaves(tree) -> dict:
    """{path: float32 numpy array} of every leaf."""
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): np.asarray(x, np.float32)
            for p, x in flat}


def diff_norms(a, b) -> dict:
    import jax
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype("float32") - y.astype("float32"), a, b))


def worst_gap(prog: dict, ref: dict, keep) -> float:
    """Widest |norm_prog - norm_ref| over the kept leaves, each against
    the larger of its own reference norm and the median leaf's."""
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def worst_diff(prog: dict, ref: dict, ref_norms: dict, keep) -> float:
    """Widest norm of (program leaf - reference leaf) over the kept
    leaves, each against the larger of its own reference norm and the
    median leaf's."""
    med = float(np.median([ref_norms[k] for k in keep]))
    return max(float(np.linalg.norm(prog[k] - ref[k]))
               / max(ref_norms[k], med) for k in keep)


class Session:
    """Set-up, window and check of one training cell in one process."""

    def __init__(self, run):
        self.run = run
        self.cell = run.cell
        self.batch = run.cell.settings["batch"]
        self.ahead = run.cell.settings["ahead"]
        self.seq_len = run.cell.traffic["seq_len"]
        self.opt = run.cell.traffic["optimizer"]
        self.readings = {}

    def feed(self, step: int):
        import jax
        with self.run.span("bench.batch"):
            return jax.device_put(corpus.train_batch(
                self.run.seed, step, self.batch, self.seq_len))

    def dispatch(self, step: int):
        """Build and upload ``step``'s batch, then dispatch the step."""
        batch = self.feed(step)
        with self.run.span("bench.train_step"):
            self.params, self.opt_state, metrics = self.step_fn(
                self.params, self.opt_state, batch)
        return metrics["loss"]

    def fetch(self, loss) -> float:
        with self.run.span("bench.fetch"):
            return float(loss)

    def setup(self):
        import jax
        from repro.training import optimizer as opt_lib
        from repro.training import train_step

        conf = self.cell.conf
        cfg = harness.program_config(conf, self.cell.model)
        ocfg = opt_lib.AdamWConfig(**self.opt)
        self.params0 = weights.make(self.cell.model, conf, self.run.seed)
        self.params = self.params0
        self.opt_state = jax.jit(functools.partial(opt_lib.init, ocfg))(
            self.params)
        self.step_fn = jax.jit(train_step.make_train_step(cfg, ocfg))
        self.next_step = 1
        kept = {}

        def keep_grad(step):
            # the clipped gradient as the optimizer received it, taken
            # from its state right after step 1 is dispatched
            if step == 1:
                kept["grad"] = jax.tree.map(lambda m: m / (1.0 - ocfg.b1),
                                            self.opt_state.mu)

        self.readings["loss"] = self.drive(
            lambda: self.next_step <= CHECKED_STEPS, keep_grad)
        self.readings["grad"] = leaf_norms(kept["grad"])
        self.readings["grad_tree"] = host_leaves(kept.pop("grad"))
        self.readings["change"] = diff_norms(self.params, self.params0)
        self.params0 = None

    def drive(self, more, after_dispatch=None) -> list:
        """The training loop of set-up and of the window: while ``more()``
        holds, keep ``self.ahead`` steps dispatched and fetch the oldest
        one's loss; once it fails, fetch the rest.  Returns the losses
        fetched, in step order, with no step left in flight."""
        losses, pending = [], collections.deque()
        while True:
            while len(pending) < self.ahead and more():
                pending.append(self.dispatch(self.next_step))
                if after_dispatch is not None:
                    after_dispatch(self.next_step)
                self.next_step += 1
            if not pending:
                return losses
            losses.append(self.fetch(pending.popleft()))

    def window(self) -> dict:
        run = self.run
        secs = run.seconds
        losses, traced0 = [], None
        t0 = time.perf_counter()
        t_end = t0 + secs
        if run.trace:
            # the traced part opens with no step in flight, so that it
            # holds the device time of exactly the steps counted
            t_mark = t0 + max(0.0, secs - harness.TRACE_SECONDS)
            losses += self.drive(lambda: time.perf_counter() < t_mark)
            run.trace_start()
            traced0 = len(losses)
        losses += self.drive(lambda: time.perf_counter() < t_end)
        # the window closes when every step in flight at ``t_end`` has
        # returned its loss: every step dispatched in it is counted, over
        # all of its time, so neither a step cut short nor a host stall
        # at the close goes uncounted
        elapsed = time.perf_counter() - t0
        done = len(losses)
        if traced0 is not None:
            run.trace_stop()
        tokens = self.batch * self.seq_len
        harness.log(f"window: {done} steps of {tokens} tokens completed "
                    f"in {elapsed:.3f} s")
        layer = {"counters": None}
        if traced0 is not None:
            layer["counters"] = {"steps": done - traced0,
                                 "tokens": (done - traced0) * tokens}
        return {"attempted": done,
                "failed": sum(not math.isfinite(x) for x in losses),
                "metrics": {"train_tok_s": done * tokens / elapsed},
                "layer_ctx": layer}

    def check(self) -> dict:
        self.params = self.opt_state = self.step_fn = None
        gc.collect()
        ref = follow_reference(self.cell.model, self.cell.conf,
                               self.run.seed, self.batch, self.seq_len,
                               self.opt, self.cell.settings["reference_rows"])
        return compare(self.readings, ref, self.cell.settings["limits"])


def follow_reference(model, conf, seed, batch, seq_len, opt, rows,
                     control: bool = False) -> dict:
    """The reference's readings over the checked steps, with the same
    weights and batches as the program."""
    import jax
    import jax.numpy as jnp
    full = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
            "grad_clip": 1.0, "warmup_steps": 100, "total_steps": 10000,
            "min_lr_ratio": 0.1}
    full.update(opt)
    stored = weights.make(model, conf, seed)
    p0 = jax.tree.map(lambda a: a.astype(jnp.float32), stored)
    store_dtype = jax.tree.leaves(stored)[0].dtype
    del stored
    params = p0
    mu = jax.tree.map(jnp.zeros_like, p0)
    nu = jax.tree.map(jnp.zeros_like, p0)
    out = {"loss": []}
    for step in range(1, CHECKED_STEPS + 1):
        b = {k: jnp.asarray(v) for k, v in
             corpus.train_batch(seed, step, batch, seq_len).items()}
        loss, grads = model.loss_and_grad(params, b, conf, rows,
                                          control=control)
        out["loss"].append(float(loss))
        params, mu, nu, clipped = reference.adamw(
            full, params, grads, mu, nu, step, store_dtype)
        if step == 1:
            out["grad"] = leaf_norms(clipped)
            out["grad_tree"] = host_leaves(clipped)
            out["raw_grad"] = leaf_norms(grads)
    out["change"] = diff_norms(params, p0)
    return out


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """The three numbers compared, each beside its limit."""
    med = float(np.median(list(ref["raw_grad"].values())))
    keep = [k for k, v in ref["raw_grad"].items() if v >= TINY_GRAD * med]
    loss_gap = max(abs(a - b) / abs(b) for a, b in
                   zip(prog["loss"], ref["loss"]))
    harness.log(f"losses program {prog['loss']} reference {ref['loss']}; "
                f"{len(keep)} of {len(ref['raw_grad'])} leaves compared")
    return {name: {"value": value, "limit": limits[name]} for name, value in
            (("loss_gap", loss_gap),
             ("grad_norm_gap", worst_gap(prog["grad"], ref["grad"], keep)),
             ("change_norm_gap", worst_gap(prog["change"], ref["change"],
                                           keep)),
             ("grad_diff", worst_diff(prog["grad_tree"], ref["grad_tree"],
                                      ref["grad"], keep)))
            if name in limits}
