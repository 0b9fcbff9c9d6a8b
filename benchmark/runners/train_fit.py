"""Training cells (traffic kind ``train_fit``): the net's ``fit`` over a
host iterator of seeded batches. The configuration's ``model`` names the
file that builds the net (``models/<model>.py``) and the plain reference
(``reference/<model>.py``); nothing here knows one model from another.

Set-up builds ONE object (the net with its compiled step and state),
drives it from the seed through its first steps through the window's own
call and feed, and hands that same object to the window. The plain
reference follows those steps once the window has closed.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from benchmark import compare, harness, weights


# ------------------------------------------------------------------ the feed
class Feed:
    """Host iterator over the pool, in order, round and round: ``steps``
    batches, or batches until the clock passes ``until``."""

    def __init__(self, pool, batch: int, start: int = 0):
        self.x, self.y = pool
        self.batch = batch
        self.n = len(self.x) // batch
        self.pos = start
        self.count = 0
        self._steps: Optional[int] = None
        self._until: Optional[float] = None

    def take(self, steps: int) -> "Feed":
        self._steps, self._until, self.count = steps, None, 0
        return self

    def until(self, deadline: float) -> "Feed":
        self._steps, self._until, self.count = None, deadline, 0
        return self

    def batch_at(self, i: int):
        j = (i % self.n) * self.batch
        return self.x[j:j + self.batch], self.y[j:j + self.batch]

    def __iter__(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        while True:
            if self._steps is not None and self.count >= self._steps:
                return
            if self._until is not None and \
                    time.perf_counter() >= self._until:
                return
            x, y = self.batch_at(self.pos)
            self.pos += 1
            self.count += 1
            yield DataSet(x, y)


# --------------------------------------------------------------- the program
class Program:
    """The system under test: the net ``models/<model>.py`` builds from
    the configuration, its weights drawn by the benchmark, and the entry
    the window drives."""

    def __init__(self, cell, seed: int):
        import jax
        cfg, traffic = cell.config, cell.traffic
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.model = cell.model()
        self.specs = cell.reference().param_specs(cfg)
        net = self.model.build(cfg)
        flat = weights.make_weights(self.specs, seed, np.float32)
        weights.check_tree_matches(flat, net.params)
        for vertex, leaves in weights.as_tree(flat).items():
            net.params[vertex] = leaves
        self.net = net
        rows = traffic["distinct_batches"] * traffic["global_batch"]
        self.feed = Feed(self.model.batches(cfg, rows, seed),
                         traffic["global_batch"])
        self._jax = jax

    def fit(self, feed: Feed) -> None:
        """The window's own call."""
        self.net.fit(feed, epochs=1)

    def sync(self) -> None:
        n = self.net
        self._jax.block_until_ready((n.params, n.state, n.updater_state))

    # -- the first steps, followed by the reference ----------------------
    def checked_steps(self) -> dict:
        """Drive the first ``checked_steps`` steps through ``fit`` and
        read, without leaving the window's path: each step's loss, the
        per-leaf norm of the first gradient as the optimizer got it (from
        its state after one step, as the model's file works it out), and
        the per-leaf norm of the parameters' change after the steps."""
        from deeplearning4j_tpu.optimize.listeners import TrainingListener

        steps = self.traffic["checked_steps"]
        gradient_norms, change_norms = self._norm_programs()

        class Probe(TrainingListener):
            def __init__(self):
                self.losses, self.grad1 = [], None

            def iteration_done(self, model, iteration, score):
                self.losses.append(score)
                if len(self.losses) == 1:
                    self.grad1 = gradient_norms(model.updater_state)

        probe = Probe()
        self.net.set_listeners(probe)
        try:
            self.fit(self.feed.take(steps))
        finally:
            self.net.set_listeners()
        change = change_norms(self.net.params,
                              weights.seed_key_data(self.seed))
        return {"losses": [float(v) for v in probe.losses],
                "grad1_norms": {k: float(v)
                                for k, v in probe.grad1.items()},
                "change_norms": {k: float(v) for k, v in change.items()}}

    def _norm_programs(self):
        """Per-leaf norms, taken on the device: of the gradient the
        optimizer got and of the parameters' distance from the seed's
        weights, redrawn inside the program."""
        if getattr(self, "_norms", None) is None:
            import jax
            import jax.numpy as jnp
            cfg, first_gradient = self.cfg, self.model.first_gradient
            specs = tuple(self.specs)

            @jax.jit
            def gradient_norms(updater_state):
                return {f"{a}/{b}": jnp.sqrt(jnp.sum(jnp.square(x)))
                        for a, leaves in
                        first_gradient(cfg, updater_state).items()
                        for b, x in leaves.items()}

            @jax.jit
            def change_norms(params, key_data):
                p0 = weights.draw_leaves(specs, key_data, jnp.float32)
                return {f"{a}/{b}": jnp.sqrt(jnp.sum(jnp.square(
                    x - p0[f"{a}/{b}"])))
                    for a, leaves in params.items()
                    for b, x in leaves.items()}

            self._norms = (gradient_norms, change_norms)
        return self._norms

    def release(self) -> None:
        """Free the program's device state before the reference runs."""
        self.net.params = self.net.state = self.net.updater_state = None
        self.net = None


def reference_readings(cell, seed: int, feed: Feed, low: bool = False,
                       rows: Optional[int] = None) -> dict:
    """The plain reference (``low``: the 8-bit control; ``rows``: the
    half-batch fault) over the same first steps from the same weights."""
    import jax.numpy as jnp
    cfg, ref = cell.config, cell.reference()
    params0 = weights.make_weights(ref.param_specs(cfg), seed, jnp.float32)
    batches = [feed.batch_at(i)
               for i in range(cell.traffic["checked_steps"])]
    return ref.train_readings(cfg, params0, batches, low=low, rows=rows)


# ------------------------------------------------------------------ one run
def run(cell, args, devices, clock0: float, tracer=None,
        control: bool = False) -> dict:
    """One run of a training cell. Returns the record the harness turns
    into the result line. ``control`` (benchmark/limits.py) also reads the
    8-bit control and the half-batch fault, put in the program's place."""
    from deeplearning4j_tpu import monitoring
    from deeplearning4j_tpu.monitoring import runtime

    traffic = cell.traffic
    monitoring.ensure_started()
    compiles = monitoring.global_registry().get(runtime.COMPILE_COUNTER)

    prog = Program(cell, args.seed)
    prog_readings = prog.checked_steps()
    prog.sync()
    compiles_before = compiles.total()

    t0 = time.perf_counter()
    if tracer is not None:
        tracer.arm(t0)
    prog.fit(prog.feed.until(t0 + args.seconds))
    prog.sync()
    t1 = time.perf_counter()
    if tracer is not None:
        tracer.finish()
    steps = prog.feed.count
    compiled_in_window = int(compiles.total() - compiles_before)
    last_loss = float(prog.net.score_value)

    record = {
        "setup_s": t0 - clock0,
        "window_s": t1 - t0,
        "steps": steps,
        "samples": steps * traffic["global_batch"],
        "attempted": steps,
        "failed": 0 if np.isfinite(last_loss) else steps,
        "compiles_in_window": compiled_in_window,
        "last_loss": last_loss,
        "end_to_end": {
            "train_samples_per_s":
                steps * traffic["global_batch"] / (t1 - t0)},
    }
    record["memory_peak_bytes"] = harness.peak_memory(devices)
    feed = prog.feed
    prog.release()

    ref_readings = reference_readings(cell, args.seed, feed)
    limits = cell.limits
    checks = compare.training_checks(prog_readings, ref_readings, limits)
    checks.append(compare.Check("compiles_in_window", compiled_in_window,
                                0, exact=True))
    checks.append(compare.Check("nonfinite_loss_steps", record["failed"],
                                0, exact=True))
    record["checks"] = checks
    record["readings"] = {
        "program": {c.name: c.value for c in checks},
        "worst_grad1_leaves": compare.worst_leaves(
            prog_readings["grad1_norms"], ref_readings["grad1_norms"]),
        "worst_change_leaves": compare.worst_leaves(
            prog_readings["change_norms"], ref_readings["change_norms"],
            compare.moved_leaves(ref_readings["grad1_norms"])),
        "losses": [prog_readings["losses"], ref_readings["losses"]]}
    # every leaf's norms too, for the look a limit needs
    record["readings"]["raw"] = {"program": prog_readings,
                                 "reference": ref_readings}
    if control:
        batch = traffic["global_batch"]
        for name, kw in (("control_fp8", {"low": True}),
                         ("fault_half_batch", {"rows": batch // 2})):
            got = reference_readings(cell, args.seed, feed, **kw)
            record["readings"][name] = {
                c.name: c.value for c in
                compare.training_checks(got, ref_readings, limits)}
            record["readings"]["raw"][name] = got
    return record
