"""What every driver gets from the harness: the cell's data, the
seeded keys, the clock, the window's bookkeeping."""

import dataclasses
import time

import numpy as np

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(phase, **fields):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class CompileCounter:
    """Counts XLA compilations (cache loads included: either way a
    program was not ready) through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_, **__):
        if name == _COMPILE_EVENT:
            self.n += 1


@dataclasses.dataclass
class Run:
    """One invocation of one cell."""
    cell: dict            # the entry of BENCHMARK.json's workloads
    config: dict          # the configuration's file
    traffic: dict         # the traffic (job) file
    devices: list
    seed: int
    seconds: float
    trace_dir: str        # "" unless --trace 1
    scratch: str          # a directory of the run's own, in the checkout
    compiles: CompileCounter
    reference_s: float = 0.0   # spent in the plain reference: not set-up
    t_process: float = dataclasses.field(default_factory=time.perf_counter)

    def key(self, stream: int):
        """Seeded PRNG key number ``stream`` (0 weights, 1 data)."""
        import jax

        return jax.random.fold_in(jax.random.PRNGKey(self.seed), stream)

    def mark(self, reached):
        """One line for each stage of set-up as it is reached, with the
        seconds since the process started (the reference's included):
        where ``setup_s`` goes, and which stage moved when it moves."""
        log("setup", reached=reached,
            t=f"{time.perf_counter() - self.t_process:.2f}")

    def timed_reference(self, fn):
        t0 = time.perf_counter()
        out = fn()
        self.reference_s += time.perf_counter() - t0
        return out

    @property
    def traced(self):
        return bool(self.trace_dir)

    def start_trace(self):
        """The device's ops only.  The host tracer stays off: in the
        ResNet cell it recorded every 4 KiB ``Transpose`` of the feed's
        host-side relayout (916,168 events in 12 iterations, 411 MB) and
        slowed the iteration it was there to measure from 0.31 to 0.84 s
        (PERF.md, PR 23)."""
        import jax

        if not self.traced:
            return
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 0
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def stop_trace(self):
        import jax

        if self.traced:
            jax.profiler.stop_trace()


class Window:
    """The measured window: iteration ends on the host clock, one
    ``block_until_ready`` at the close, and the program's compilations
    counted from open to close."""

    def __init__(self, run: Run, units_per_iteration: int):
        self.run = run
        self.units = units_per_iteration
        self.ends = []          # perf_counter at each iteration's end
        self.losses = []        # what each iteration reported
        self.t_open = self.t_close = None
        self._compiles_at_open = None

    def open(self):
        self._compiles_at_open = self.run.compiles.n
        self.t_open = time.perf_counter()

    def last(self, in_flight=0):
        """Whether the iteration about to end is the window's last:
        ``--seconds`` have passed, or a traced run has the few steps its
        traffic file asks for (``in_flight`` dispatched, not yet ended)."""
        if time.perf_counter() - self.t_open >= self.run.seconds:
            return True
        return self.run.traced and self.iterations + in_flight \
            >= self.run.traffic["trace_steps"]

    def end_iteration(self, loss=None):
        self.ends.append(time.perf_counter())
        self.losses.append(loss)

    def close(self, *block_on):
        import jax

        jax.block_until_ready(block_on)
        self.t_close = time.perf_counter()
        self.compiles_inside = self.run.compiles.n - self._compiles_at_open

    # -- what the end-to-end metrics are made of ---------------------- #
    @property
    def seconds(self):
        return self.t_close - self.t_open

    @property
    def iterations(self):
        return len(self.ends)

    @property
    def rate(self):
        """Units whose update finished inside the window, over all of
        the window's seconds."""
        return self.iterations * self.units / self.seconds

    @property
    def intervals_ms(self):
        return np.diff([self.t_open] + self.ends) * 1e3

    def failed(self):
        """Iterations of the window whose loss is not finite (read only
        now, after the close: nothing waits on a loss inside)."""
        return sum(1 for v in self.losses
                   if v is not None and not np.isfinite(np.asarray(v)))


@dataclasses.dataclass
class Outcome:
    """What a driver hands back."""
    correct: bool
    window: Window
    memory_peak_bytes: int
    compared: dict        # check.gaps(): what decided ``correct``
    readings: tuple       # (program's, reference's) ``follow`` readings
    spans: list = dataclasses.field(default_factory=list)
    # whatever the per-layer readers of this kind of cell need
    facts: dict = dataclasses.field(default_factory=dict)


def program_bytes(compiled):
    """Peak bytes of one compiled program on one device: arguments,
    results that are not donated arguments, and XLA's temporaries.  The
    runtime's ``peak_bytes_in_use`` leaves the temporaries out on this
    chip (PERF.md, PR 21), and would count the plain reference too."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def same_layout(params, shapes, builder):
    """The benchmark makes the weights itself, in the layout the
    program's own ``builder`` would: refuse to go on if that moved."""
    import jax

    if jax.tree.map(lambda a: a.shape, params) != \
            jax.tree.map(lambda a: a.shape, shapes):
        raise SystemExit("the reference's weights no longer have the "
                         f"layout of {builder}")


def build_optimizer(spec):
    """``optax.<name>(**args)`` straight from the configuration's
    ``optimizer`` block: another optimizer is another file."""
    import optax

    return getattr(optax, spec["name"])(**spec["args"])


def first_gradient_norms(opt_state, spec):
    """``{leaf: norm}`` of the first gradient as the optimizer got it,
    worked out from its state after one step.  Where it is kept is the
    configuration's to say (``optimizer.first_gradient``): the field of
    the state that holds it and what to multiply by -- SGD's momentum
    ``trace`` is the gradient itself, Adam's ``mu`` is ``1 - b1`` of it."""
    import jax

    from benchmarks.reference.common import leaf_norms, to_floats

    kept = find_field(opt_state, spec["state_field"])
    if kept is None:
        raise SystemExit(f"no field {spec['state_field']!r} in the "
                         f"optimizer's state: {type(opt_state)}")
    return {k: v * spec["times"]
            for k, v in to_floats(jax.jit(leaf_norms)(kept)).items()}


def find_field(opt_state, field):
    """The first ``field`` of an optax state, looked for depth first
    through its tuples, lists and dicts."""
    if hasattr(opt_state, field):
        return getattr(opt_state, field)
    children = (opt_state.values() if isinstance(opt_state, dict)
                else opt_state if isinstance(opt_state, (tuple, list))
                else ())
    for child in children:
        found = find_field(child, field)
        if found is not None:
            return found
    return None
