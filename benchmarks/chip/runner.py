"""One run of one cell: set-up, the measured window, the check.

The entry the window drives is ``repro.launch.scheduler.run_schedule`` over
the engine ``repro.launch.serve.make_engine`` builds, wrapped in the
benchmark's `EngineProxy`.  The queue outlasts the window, so the batch
stays full: an offline batch job.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import pathlib
import shutil
import typing
from types import ModuleType
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

import engine_proxy as ep
import metric_readers
import model_weights as mw
import trace_reduce
import workload

HERE = pathlib.Path(__file__).resolve().parent
RUN_DIR = HERE / ".run"
FAMILIES = HERE / "families"


# ---- compilations inside the window ------------------------------------

class CompileWatch:
    """Counts JAX's compile events while ``armed``."""

    def __init__(self):
        self.armed = False
        self.events: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, duration: float, **kw) -> None:
        if self.armed and "/compile" in event:
            self.events[event] = self.events.get(event, 0) + 1

    @property
    def count(self) -> int:
        return sum(n for e, n in self.events.items()
                   if e.endswith("backend_compile_duration")
                   or e.endswith("jaxpr_trace_duration"))


_WATCH: Optional[CompileWatch] = None


def compile_watch() -> CompileWatch:
    global _WATCH
    if _WATCH is None:
        _WATCH = CompileWatch()
    return _WATCH


# ---- the model family and the program's configuration ------------------

def program_family(conf: Dict) -> str:
    """The family of the program's own entry for ``conf['program']``."""
    from repro.configs import get_arch
    return get_arch(conf["program"]).config.family


@functools.lru_cache(maxsize=None)
def _load_family(path: pathlib.Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(f"family_{path.stem}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(conf: Dict) -> ModuleType:
    """The module of the family the configuration states,
    ``families/<conf['family']>.py``: its weights, reference and counts.

    The file states the family, not the program, so that a change to the
    program cannot switch the reference that checks it; a run whose
    program is of another family is refused."""
    name = conf["family"]
    path = FAMILIES / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in FAMILIES.glob("*.py"))
        raise ValueError(f"no model family {name!r}: {FAMILIES} holds "
                         f"{known}")
    program = program_family(conf)
    if program != name:
        raise ValueError(f"the configuration states family {name!r}, the "
                         f"program's {conf['program']!r} is {program!r}")
    return _load_family(path)


def _section(field: str, base, value: Dict):
    """``value`` set over the program's own ``base`` for a field that holds
    a dataclass (``moe``, ``ssm``); every key must be one of its fields."""
    hint = typing.get_type_hints(type(base))[field]
    cls = next((t for t in typing.get_args(hint) or (hint,)
                if dataclasses.is_dataclass(t)), None)
    if cls is None:
        raise ValueError(f"model key {field!r} takes no section")
    unknown = set(value) - {f.name for f in dataclasses.fields(cls)}
    if unknown:
        raise ValueError(f"unknown keys in section {field!r}: "
                         f"{sorted(unknown)}")
    own = getattr(base, field)
    return cls(**value) if own is None else dataclasses.replace(own, **value)


def program_config(conf: Dict):
    """The program's ModelConfig: its own entry for ``conf['program']``
    with every key of ``conf['model']`` set as the file states it; a
    section (a dict) is set key by key over the program's own value."""
    from repro.configs import get_arch
    base = get_arch(conf["program"]).config
    model = {k: _section(k, base, v) if isinstance(v, dict) else v
             for k, v in conf["model"].items()}
    return base.replace(**model)


def make_params(seed: int, m, pcfg,
                program_params: Callable = mw.program_params):
    """The program's parameters from the seed, made on the device in one
    jitted call by a family's ``program_params`` (the dense one's unless
    another is given)."""
    from repro.launch import steps as st
    key = mw.base_key(seed)
    shapes = jax.eval_shape(st.init_params_fn(pcfg), jax.random.PRNGKey(0))
    params = jax.jit(lambda k: program_params(k, m, shapes))(key)
    return jax.block_until_ready(params)


# ---- the run's record, as the metric readers see it --------------------

@dataclasses.dataclass
class Run:
    cell: str
    family: ModuleType                # families/<name>.py: counts of ``m``
    m: object                         # the family's Model
    peaks: Dict[str, float]
    window_s: float
    decode_calls: List[dict]          # in the window
    admissions: List[tuple]           # (t, prompt_len) in the window
    admit_span_s: float               # admit entry -> next call, summed
    itl_ms: List[float]               # token gaps in the window
    # the program's own counts (engine ``counters``), closing values less
    # opening ones: over the whole window, also in a traced run; {} for an
    # engine that keeps none.  A reader takes ``counters.get(name)``.
    counters: Dict[str, float]
    trace: Optional[trace_reduce.TraceSummary] = None


@dataclasses.dataclass
class Outcome:
    setup_s: float
    window_s: float
    attempted: int
    failed: int
    tokens: int
    itl_ms: List[float]
    compiles: int
    compile_events: Dict[str, int]
    memory_peak: Optional[int]
    run: Run
    served: Dict[int, np.ndarray]
    finished: List[int]
    queue: workload.Queue


def _window_record(proxy: ep.EngineProxy, t1: float):
    """What the proxy saw from the window's opening to ``t1``."""
    t0 = proxy.t_open
    calls = [c for c in proxy.decode_calls if t0 <= c["t"] < t1]
    reqs = proxy.requests.values()
    admissions = [(r.t_admit, r.prompt_len) for r in reqs
                  if t0 <= r.t_admit < t1]
    # an admission's span runs to the next engine call: its prefill
    # dispatch plus the first token's selection and read-back
    times = [t for _, t in proxy.calls] + [t1]
    kinds = [k for k, _ in proxy.calls]
    admit_s = sum(min(times[i + 1], t1) - times[i]
                  for i, k in enumerate(kinds)
                  if k == "admit" and t0 <= times[i] < t1)
    tokens = len(admissions) + sum(c["slots"] for c in calls)
    itl: List[float] = []
    failed = 0
    for r in reqs:
        ts = [r.t_admit] + [proxy.decode_calls[i]["t"] for i in r.decodes]
        itl.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:])
                   if a >= t0 and b < t1)
        if (r.t_release is not None and t0 <= r.t_release < t1
                and len(r.decodes) + 1 < r.gen):
            failed += 1
    return calls, admissions, admit_s, tokens, itl, failed


def run_cell(cell: str, traffic: Dict, conf: Dict, *, seed: int,
             seconds: float, trace: bool, t_start: float,
             peaks: Dict[str, float],
             wrap_engine: Optional[Callable] = None,
             keep_trace: Optional[pathlib.Path] = None) -> Outcome:
    """Set up, drive the window, and free the program's state.

    A traced run serves the same window as an untraced one, so that its
    check compares as much, but profiles only the window's first
    ``trace_seconds`` of the traffic file: a few steady seconds give the
    per-layer metrics, and a longer trace would not be read within a run's
    time.  The per-layer metrics read that traced part alone, the check
    the whole window.  ``wrap_engine`` (tests only) replaces the engine
    the proxy drives, to break the timed path underneath the harness.
    """
    from repro.launch import scheduler as sched
    from repro.launch import serve

    fam = family(conf)
    m = fam.Model.from_config(conf)
    pcfg = program_config(conf)
    params = make_params(seed, m, pcfg, fam.program_params)
    queue = workload.make_queue(traffic, m.vocab_size, seed)
    engine = serve.make_engine(params, pcfg, queue.prompts,
                               slots=traffic["slots"],
                               max_len=traffic["max_len"],
                               block_k=traffic["block_k"])
    if wrap_engine is not None:
        engine = wrap_engine(engine)
    watch = compile_watch()
    trace_dir = RUN_DIR / f"trace-{cell}-{seed}"
    window_span = []

    def on_open():
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # no per-call Python events
            opts.host_tracer_level = 1      # the bench.* spans only
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            window_span.append(jax.profiler.TraceAnnotation("bench.window"))
            window_span[0].__enter__()
        watch.events.clear()
        watch.armed = True

    def stop_trace():
        if window_span:
            window_span.pop().__exit__(None, None, None)
            jax.profiler.stop_trace()

    proxy = ep.EngineProxy(engine, queue.prompts, queue.gens,
                           seconds=seconds, on_open=on_open,
                           mark_after=traffic["trace_seconds"] if trace
                           else None, on_mark=stop_trace)
    if trace:
        proxy.annotate = jax.profiler.TraceAnnotation
    try:
        sched.run_schedule(proxy, queue.prompts, gens=queue.gens,
                           warmup=True)
        raise RuntimeError("the schedule ended before the window closed")
    except ep.WindowClosed:
        pass
    finally:
        watch.armed = False
        stop_trace()

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    served = proxy.served_tokens()
    counters = proxy.window_counters()
    _, _, _, tokens, itl, failed = _window_record(proxy, proxy.t_close)
    t_layer = proxy.t_mark or proxy.t_close
    calls, admissions, admit_s, _, layer_itl, _ = _window_record(proxy,
                                                                 t_layer)
    finished = [rid for rid, r in proxy.requests.items()
                if r.t_release is not None and len(r.decodes) + 1 >= r.gen]
    window_s = proxy.t_close - proxy.t_open
    run = Run(cell=cell, family=fam, m=m, peaks=peaks,
              window_s=t_layer - proxy.t_open,
              decode_calls=calls, admissions=admissions,
              admit_span_s=admit_s, itl_ms=layer_itl, counters=counters)
    out = Outcome(setup_s=proxy.t_open - t_start, window_s=window_s,
                  attempted=proxy.admitted, failed=failed, tokens=tokens,
                  itl_ms=itl, compiles=watch.count,
                  compile_events=dict(watch.events), memory_peak=peak,
                  run=run, served=served, finished=finished, queue=queue)
    del engine, proxy, params
    gc.collect()
    if trace:
        planes = trace_reduce.read_planes(trace_reduce.xplane_file(trace_dir))
        if keep_trace is not None:
            keep_trace.mkdir(parents=True, exist_ok=True)
            (keep_trace / f"{cell}-{seed}.describe.json").write_text(
                json.dumps(trace_reduce.describe(planes), indent=1))
            (keep_trace / f"{cell}-{seed}.excerpt.json").write_text(
                json.dumps(trace_reduce.excerpt(planes, 0.25)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.trace = trace_reduce.summarize(planes)
    return out


# ---- correctness ----------------------------------------------------------

def sample(out: Outcome, traffic: Dict, seed: int) -> List[int]:
    """Finished requests to compare: the one served most tokens, then
    others in an order drawn from the seed until ``sample_tokens`` served
    tokens are in the sample."""
    done = [rid for rid in out.finished if len(out.served[rid]) > 0]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(out.served[r]), -r))
    rest = sorted(set(done) - {longest})
    picked, n = [longest], len(out.served[longest])
    for rid in workload.rng_for(seed, 2).permutation(rest).tolist():
        if n >= traffic["sample_tokens"]:
            break
        picked.append(rid)
        n += len(out.served[rid])
    return sorted(picked)


def check(out: Outcome, traffic: Dict, conf: Dict, seed: int, *,
          control: bool = False) -> Dict[str, np.ndarray]:
    """Reference gaps over the sampled requests' served tokens, by the
    reference of the configuration's family."""
    fam = family(conf)
    m = fam.Model.from_config(conf)
    gaps, ctrl = [], []
    for rid in sample(out, traffic, seed):
        r = fam.compare(seed, m, out.queue.prompts[rid], out.served[rid],
                        control=control)
        gaps.append(r["gap"])
        if control:
            ctrl.append(r["ctrl_gap"])
    res = {"gap": np.concatenate(gaps) if gaps else np.zeros(0)}
    if control:
        res["ctrl_gap"] = np.concatenate(ctrl) if ctrl else np.zeros(0)
    return res


def verdict(out: Outcome, gap: np.ndarray, traffic: Dict) -> tuple:
    """``correct`` and the numbers it compared, each beside its limit.

    The cell's traffic file holds the limits: ``gap_limit``, the widest
    gap in logits by which a served token's reference logit may lie below
    the reference's best, and ``min_compared``."""
    limit = traffic["gap_limit"]
    widest = float(gap.max()) if gap.size else None
    checks = {
        "widest_logit_gap": {"value": widest, "limit": limit},
        "tokens_compared": {"value": int(gap.size),
                            "limit": traffic["min_compared"]},
        "failed_requests": {"value": out.failed, "limit": 0},
    }
    correct = (widest is not None and widest <= limit
               and gap.size >= traffic["min_compared"] and out.failed == 0)
    return bool(correct), checks


def metrics(out: Outcome, workload_entry: Dict, bench: Dict,
            trace: bool) -> Dict[str, Dict]:
    """The cell's end-to-end metrics (trace 0) or per-layer ones (1)."""
    cell = workload_entry["name"]
    if trace:
        return metric_readers.read_all(bench["per_layer"], cell, out.run)
    values = {
        "setup_s": out.setup_s,
        "tok_s": out.tokens / out.window_s,
        "itl_p95_ms": (float(np.percentile(out.itl_ms, 95))
                       if out.itl_ms else None),
    }
    res = {}
    for spec in bench["end_to_end"]:
        if cell in spec.get("workloads", [cell]) and \
                values.get(spec["name"]) is not None:
            res[spec["name"]] = {"value": values[spec["name"]],
                                 "unit": spec["unit"]}
    return res

