"""chipbench: the benchmark's one command.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, on the machine it is started on. It finds the cell's
configuration, traffic and metric files by the names in `BENCHMARK.json`
(`manifest.py`), and by the names those files give the input module, the
generator, the loop, the check and the readers (`inputs/`, `generators/`,
`loops/`, `checks/`, `readers/`). The configuration's input module makes
every input from `--seed` and says what a job's arguments are; the
harness runs one whole job of the cell as the warm-up (that is what
compiles), then drives the program's own entry,
`avenir_tpu.runner.run_from_cli`, in the mix's loop for `--seconds`
seconds: with `closed`, one client, a whole job from input files to the
complete output file, again and again, each job on the next of the
cell's input files; a job that has started when the time runs out is
finished and counted. With `--trace 1` the window is exactly one job,
under `jax.profiler` and `obs.capture()`.

When the window has closed and the peak memory has been read, the output
files the timed jobs wrote are compared with the plain reference by the
check the configuration names (`checks/<reference.kind>.py`). Nothing
here knows a job family: which files a job reads, what it writes and
what is compared are the input module's and the check's. The last line
of standard output is the result; without an accelerator, or with fewer
chips than the cell asks for, there is no result and the exit code is
not 0.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()            # process start, as near as Python gets

import argparse                      # noqa: E402
import ast                           # noqa: E402
import contextlib                    # noqa: E402
import gc                            # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import re                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import compare, manifest, reduce, xtrace  # noqa: E402

WORK_DIR = ".chipbench_work"         # inside the checkout, git-ignored
JOB_ANNOTATION = "chipbench.job"
ROOT_SPAN = "job.cli"                # the program's one span around a job
#: named rows of a chip's idle time in `breakdown.idle_gaps` and in
#: `notes.by_chip`, and `rest` besides: the driver keeps 10 rows a list
IDLE_ROWS, CHIP_ROWS = 9, 5


class NoChip(RuntimeError):
    """The machine does not hold what the cell asks for."""


# ------------------------------------------------------------------ set-up
def look_for_chip(chips: int) -> Dict:
    """The device as JAX reports it; raises NoChip on the CPU or with
    fewer chips than `chips`. Applies the program's own device rule,
    which also places the persistent compile cache."""
    import jax

    from avenir_tpu.utils.devices import DeviceUnavailable, require_backend

    try:
        require_backend()
    except DeviceUnavailable as exc:
        raise NoChip(str(exc)) from exc
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("JAX runs on the CPU: the benchmark measures a chip")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


#: the settings of the runtime a configuration may state, each with the
#: values it may take: this one flag and nothing else
ALLOWED_ENVIRONMENT = {
    "LIBTPU_INIT_ARGS": re.compile(r"--xla_tpu_scoped_vmem_limit_kib=\d{1,6}"),
}


def source_has_keyword(path: str, function: str, keyword: str) -> bool:
    """Whether some call inside `function` of the Python file `path` is
    given the keyword argument `keyword` (or the file is not there)."""
    try:
        with open(path) as fh:
            tree = ast.parse(fh.read())
    except OSError:
        return False
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == function:
            return any(isinstance(kw, ast.keyword) and kw.arg == keyword
                       for kw in ast.walk(node))
    return False


def apply_environment(cfg: Dict, root: str = ROOT,
                      environ=os.environ) -> Dict[str, str]:
    """The deployment's settings of the runtime, stated in the
    configuration's file under `environment`, set before JAX starts;
    returns what was applied, for the result line. Only what
    `ALLOWED_ENVIRONMENT` names may be stated. A setting that stands in
    for a fault of the program is withheld once the program is mended:
    `environment_until` names the file, the function and the keyword
    argument whose presence is the mend. A flag list the caller's
    environment already holds is kept, with the configuration's appended."""
    wanted = cfg.get("environment", {})
    for key, val in wanted.items():
        allowed = ALLOWED_ENVIRONMENT.get(key)
        if allowed is None or not allowed.fullmatch(val):
            raise ValueError(f"a configuration may not set {key}={val!r}")
    until = cfg.get("environment_until")
    if until and source_has_keyword(os.path.join(root, until["file"]),
                                    until["function"], until["keyword"]):
        return {}
    for key, val in wanted.items():
        have = environ.get(key, "")
        if val not in have:
            environ[key] = (have + " " + val).strip()
    return dict(wanted)


def memory_readings() -> List[Dict]:
    """Each chip's `memory_stats()` now ({} where the backend does not
    say, as on the CPU)."""
    import jax

    return [dict(d.memory_stats() or {}) for d in jax.devices()]


def fullest_chip(readings: List[List[Dict]]) -> Dict[str, int]:
    """`reduce.hbm_peak` of the chip that held most, from the readings
    taken at the start of each job of the window and at its close."""
    chips = [reduce.hbm_peak([r[i] for r in readings])
             for i in range(len(readings[0]))]
    return max(chips, key=lambda c: c["peak"])


def default_entry(argv: List[str]) -> None:
    """The program's own entry, its stdout line sent to stderr so that the
    result stays the last line of standard output."""
    from avenir_tpu.runner import run_from_cli

    with contextlib.redirect_stdout(sys.stderr):
        run_from_cli(argv)


# ------------------------------------------------------------------ the run
def run_cell(cell: manifest.Cell, man: manifest.Manifest, seed: int,
             seconds: float, traced: bool, device: Dict,
             entry: Callable[[List[str]], None] = default_entry,
             work_root: str = os.path.join(ROOT, WORK_DIR),
             t0: float = _T0, environment: Optional[Dict] = None) -> Dict:
    """Set-up, window and comparison of one run; returns the result
    object. `entry` is what the window drives (a test puts a broken one in
    its place); `device` is what `look_for_chip` found; `environment` what
    `apply_environment` applied. The loop is the one the mix names
    (`loops/<loop>.py`); the input module and the check are the ones the
    configuration names (`inputs/<inputs_kind>.py`,
    `checks/<reference.kind>.py`). Of the inputs the harness uses
    `n_files`, `out_suffix`, `argv(file_no, out)` and `warmup_argv(out)`;
    the rest is between the input module and the check."""
    loop = man.module("loops", cell.traffic["loop"])
    check = man.module("checks", cell.config["reference"]["kind"])
    work = os.path.join(work_root, cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    t_in = time.perf_counter()
    inputs = man.inputs(cell.config).Inputs(cell, seed, work)
    n_files = inputs.n_files
    t_warm = time.perf_counter()
    warm_out = os.path.join(work, "out_warmup" + inputs.out_suffix)
    entry(inputs.warmup_argv(warm_out))
    setup_s = time.perf_counter() - t0
    parts = {"before_inputs_s": t_in - t0, "inputs_s": t_warm - t_in,
             "warmup_job_s": time.perf_counter() - t_warm}

    jobs: List[Dict] = []
    memory: List[List[Dict]] = []       # every chip's stats, per reading

    def one_job(i: int) -> None:
        memory.append(memory_readings())
        file_no = i % n_files
        out = os.path.join(work, f"out_{i:03d}{inputs.out_suffix}")
        t = time.perf_counter()
        try:
            entry(inputs.argv(file_no, out))
            ok, err = True, None
        except Exception as exc:        # a failed job is counted, not fatal
            ok, err = False, repr(exc)
            print(f"chipbench: job {i} failed: {err}", file=sys.stderr)
        jobs.append({"file": file_no, "out": out, "ok": ok,
                     "seconds": time.perf_counter() - t})

    ctx: Optional[Dict] = None
    w0 = time.perf_counter()
    if traced:
        ctx = traced_job(one_job, work)
    else:
        loop.drive(one_job, seconds, cell.traffic)
    window_s = time.perf_counter() - w0
    memory.append(memory_readings())
    hbm = fullest_chip(memory)
    gc.collect()

    done = sum(1 for j in jobs if j["ok"])
    t_chk = time.perf_counter()
    numbers = check.numbers(cell, inputs, seed, jobs, warm_out)
    parts["check_s"] = time.perf_counter() - t_chk
    good, rows = compare.verdict(numbers, cell.config["check"]["limits"])
    good = good and done == len(jobs)

    dev = dict(device, memory_peak_bytes=hbm["peak"])
    if traced:
        metrics, extra = per_layer(cell, man, ctx, check.sizes(cell, inputs),
                                   device, hbm)
        dev.update(extra.pop("device"))
    else:
        values = {"job_s": window_s / max(done, 1), "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        extra = {}
    result = {"correct": bool(good), "attempted": len(jobs),
              "failed": len(jobs) - done, "metrics": metrics, "device": dev}
    result.update(extra)
    result["window_s"] = window_s
    result["memory"] = hbm
    result["environment"] = dict(environment or {})
    result["setup_parts"] = parts
    result["job_seconds"] = [j["seconds"] for j in jobs]
    result["checked"] = {
        r["name"]: {"value": r["value"], "limit": r["limit"]} for r in rows}
    result["checked"]["_seen"] = {k: v for k, v in numbers.items()
                                  if k not in result["checked"]}
    return result


def traced_job(one_job: Callable[[int], None], work: str) -> Dict:
    """Run job 0 under the profiler and the program's span capture; the
    raw material of the per-layer readers."""
    import jax

    from avenir_tpu import obs
    from avenir_tpu.utils.devices import device_report

    log_dir = os.path.join(work, "trace")
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1      # keeps TraceAnnotations, little else
    opts.python_tracer_level = 0
    before = device_report()
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with obs.capture() as rec:
            with jax.profiler.TraceAnnotation(JOB_ANNOTATION):
                one_job(0)
    finally:
        jax.profiler.stop_trace()
    after = device_report()
    events = xtrace.read_events(xtrace.newest_xplane(log_dir))
    shutil.rmtree(log_dir, ignore_errors=True)
    lo, hi = xtrace.window_of(events["annotations"], JOB_ANNOTATION)
    spans = [{"name": s.name, "t0": s.t0, "dur": s.dur, "tid": s.tid,
              "attrs": s.attrs or {}} for s in rec.spans()]
    return {"spans": spans, "devices": events["devices"],
            "window_ns": (lo, hi), "compiles": (before, after),
            "lines": events["lines"]}


def per_layer(cell: manifest.Cell, man: manifest.Manifest, ctx: Dict,
              sizes: Dict, device: Dict, hbm: Dict):
    """The cell's per-layer metrics from the traced job, each by the
    reader its file names; a reader that finds nothing leaves its metric
    out. Also `breakdown` and the device's busy time."""
    ctx.update({"sizes": sizes, "jobs": 1, "notes": {},
                "memory_peak_bytes": hbm["peak"],
                "memory_live_peak_bytes": hbm["in_use_peak"],
                "memory_reserved_bytes": hbm["reserved"],
                "peaks": reduce.load_peaks(device["kind"])})
    metrics = {}
    for m in cell.per_layer:
        spec = man.metric(m["name"])
        value = man.reader(spec["reader"])(ctx, spec.get("params", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    lo, hi = ctx["window_ns"]
    devs = list(ctx["devices"].values())
    if not devs:
        raise RuntimeError(
            "the trace holds no device operations; its planes and lines: "
            + json.dumps(ctx["lines"]))
    busy = sum(reduce.busy_ns(d["ops"], lo, hi) for d in devs) / len(devs)
    first = devs[0]["ops"]
    in_window = [e for e in first if e[1] + e[2] > lo and e[1] < hi]
    idle_of = idle_namer(ctx, lo, hi)
    breakdown = {"device_ops": reduce.top_by_name(in_window),
                 "idle_gaps": reduce.top_with_rest(idle_of(first), IDLE_ROWS)}
    ctx["notes"]["idle_by_neighbours"] = reduce.idle_by_neighbours(first, lo, hi)
    if len(devs) > 1:
        ctx["notes"]["by_chip"] = []
        for name, dev in ctx["devices"].items():
            chip_busy = reduce.busy_ns(dev["ops"], lo, hi)
            ctx["notes"]["by_chip"].append({
                "chip": name, "busy_s": chip_busy / 1e9,
                "idle_pct": 100.0 * (1.0 - chip_busy / (hi - lo)),
                "idle_gaps": reduce.top_with_rest(idle_of(dev["ops"]),
                                                  CHIP_ROWS)})
    return metrics, {"breakdown": breakdown, "notes": ctx["notes"],
                     "device": {"busy_s": busy / 1e9,
                                "window_s": (hi - lo) / 1e9}}


def idle_namer(ctx: Dict, lo: float, hi: float
               ) -> Callable[[List], Dict[str, float]]:
    """From one chip's operations to {name: idle ns} over the traced
    window, adding up to that chip's idle time: each part of each gap put
    down to the innermost span of the job's thread the host was in, or
    `no span` (`reduce.innermost_on_profiler_clock`). Where the clock join
    through `ROOT_SPAN` is refused, each gap is named by the device
    operations around it (`reduce.neighbour_gaps`).
    `notes.idle_gaps_named_by` says which, and why."""
    joined = reduce.clock_join(ctx["spans"], ROOT_SPAN, lo, hi)
    if joined is None:
        why = f"operations: not one {ROOT_SPAN} span to join the clocks by"
    else:
        root, slack = joined
        ctx["notes"]["clock_join_slack_ms"] = slack / 1e6
        why = (None if reduce.join_holds(slack, lo, hi) else
               f"operations: the clock join's slack is outside 0 to "
               f"{reduce.MAX_SLACK_SHARE} of the window")
    if why is None:
        ctx["notes"]["idle_gaps_named_by"] = "span"
        pieces = reduce.innermost_on_profiler_clock(ctx["spans"], root, lo, hi)
        return lambda ops: reduce.idle_inside(pieces,
                                              reduce.idle_gaps(ops, lo, hi))
    ctx["notes"]["idle_gaps_named_by"] = why
    return lambda ops: reduce.neighbour_gaps(ops, lo, hi)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    man = manifest.Manifest()
    cell = man.cell(args.workload)
    applied = apply_environment(cell.config)
    try:
        device = look_for_chip(cell.chips)
    except (NoChip, ImportError) as exc:
        print(f"chipbench: no run: {exc}", file=sys.stderr)
        return 2
    result = run_cell(cell, man, args.seed, args.seconds, bool(args.trace),
                      device, environment=applied)
    sys.stdout.flush()
    for name, row in result["checked"].items():
        if name != "_seen":
            print(f"chipbench: compared {name} = {row['value']} "
                  f"(limit {row['limit']})", file=sys.stderr)
    print(f"chipbench: correct = {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
