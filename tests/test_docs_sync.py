"""Doc-sync lint: every typed telemetry record kind the code can emit
must have a schema row in docs/OBSERVABILITY.md.

The record table is the contract consumers (dmp_report.py, the soak
gates, external ingestion) build against; a new `.record("kind", ...)`
call shipped without a row is an undocumented wire format. This test
greps the emitting code for literal record kinds and fails naming the
missing ones — so the fix is always "add the row", never archaeology."""

import re
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# Everywhere TelemetryRun records are emitted from: the package itself,
# the report/soak drivers, and the benchmark harnesses.
EMITTING_ROOTS = (
    REPO / "distributed_model_parallel_tpu",
    REPO / "scripts",
    REPO / "benchmarks",
)

RECORD_RE = re.compile(r'\.record\(\s*"([a-z_]+)"')
METRIC_RE = re.compile(r'\.(?:counter|gauge|histogram)\(\s*"([a-z_0-9]+)"')
WALLCLOCK_RE = re.compile(r"time\.time\(\)")


def _emitting_files() -> list[Path]:
    return [p for root in EMITTING_ROOTS for p in root.rglob("*.py")]


def _emitted_kinds() -> set[str]:
    kinds: set[str] = set()
    for path in _emitting_files():
        kinds |= set(RECORD_RE.findall(path.read_text()))
    return kinds


def _emitted_metric_names() -> set[str]:
    names: set[str] = set()
    for path in _emitting_files():
        names |= set(METRIC_RE.findall(path.read_text()))
    return names


def _documented_kinds() -> set[str]:
    """Kind names from the first column of the record-schema table in
    docs/OBSERVABILITY.md (rows like ``| `step` | ... |``; a cell may
    list several kinds)."""
    doc = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    kinds: set[str] = set()
    for line in doc.splitlines():
        if not line.startswith("|"):
            continue
        first_cell = line.split("|")[1]
        kinds |= set(re.findall(r"`([a-z_]+)`", first_cell))
    return kinds


def test_every_emitted_record_kind_is_documented():
    emitted = _emitted_kinds()
    # Sanity: the grep actually found the core kinds — an empty emitted
    # set would make this lint vacuously green. The observability-plane
    # kinds (alert: utils/alerts.py firing/resolved transitions;
    # postmortem: utils/flightrec.py bundle pointers) are pinned here so
    # a refactor that stops emitting them fails loudly too.
    # (cell: serve/fleet.py correlated-failure lifecycle — kill / sick /
    # partition / heal / grow-back — the ISSUE-17 scenario gates replay
    # these, so silently losing the kind would blind the soak runner.
    # intent / watermark / terminal: serve/journal.py write-ahead
    # journal records — the ISSUE-18 crash-recovery paths replay from
    # them, so losing a kind would silently break crash consistency.)
    assert {"run_start", "step", "failure", "recovery", "tenant",
            "alert", "postmortem", "cell", "router", "migration",
            "shed", "intent", "watermark", "terminal"} <= emitted
    missing = sorted(emitted - _documented_kinds())
    assert not missing, (
        f"telemetry record kinds emitted but missing from the "
        f"docs/OBSERVABILITY.md record table: {missing} — add a schema "
        f"row for each (kind, payload keys, writer)")


def test_every_metric_name_is_documented():
    """Same contract, one level down: every literal registry metric name
    (``counter(``/``gauge(``/``histogram(``) the package and scripts
    can emit must appear (backticked) somewhere in
    docs/OBSERVABILITY.md — the per-tenant counter semantics and the
    report both lean on these names, so an undocumented one is a wire
    format nobody can consume."""
    emitted = _emitted_metric_names()
    # Sanity: the grep found the core families.
    assert {"jax_compiles", "collective_traces", "serve_ttft_s"} <= emitted
    doc = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    documented = set(re.findall(r"`([a-z_0-9]+)", doc))
    missing = sorted(emitted - documented)
    assert not missing, (
        f"registry metric names emitted but never mentioned in "
        f"docs/OBSERVABILITY.md: {missing} — add each to the metric "
        f"tables (counters / gauges / histograms)")


def test_statusz_endpoints_and_bundle_format_are_documented():
    """The live observability plane's wire surfaces are contracts too:
    every HTTP endpoint the statusz exporter serves and every file a
    postmortem bundle contains must be named in docs/OBSERVABILITY.md —
    Prometheus scrape configs and bundle consumers build against them.
    The expected sets are read from the CODE (the handler's literal
    paths, the manifest's file list), so adding an endpoint or bundle
    file without documenting it fails here."""
    doc = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    statusz_src = (REPO / "distributed_model_parallel_tpu" / "utils"
                   / "statusz.py").read_text()
    # ANY literal "/word" path the handler compares against is a served
    # endpoint — a newly added one lands here without a whitelist edit.
    endpoints = {e for e in re.findall(r'"(/[a-z]+)"', statusz_src)}
    assert {"/metrics", "/statusz", "/healthz"} <= endpoints
    missing = sorted(e for e in endpoints if f"`{e}`" not in doc)
    assert not missing, (
        f"statusz endpoints served but missing from "
        f"docs/OBSERVABILITY.md: {missing}")
    flight_src = (REPO / "distributed_model_parallel_tpu" / "utils"
                  / "flightrec.py").read_text()
    # ANY _write("name.ext", ...) call defines a bundle member.
    bundle_files = set(re.findall(r'_write\("([a-z_]+\.[a-z]+)"',
                                  flight_src))
    assert {"manifest.json", "records.jsonl", "stacks.txt"} <= bundle_files
    missing = sorted(f for f in bundle_files if f"``{f}``" not in doc
                     and f"`{f}`" not in doc)
    assert not missing, (
        f"postmortem bundle files written but missing from "
        f"docs/OBSERVABILITY.md: {missing}")


def test_durations_never_subtract_wall_clock():
    """Monotonic-duration audit: ``time.time()`` is for ``ts`` stamps
    (cross-stream correlation), never for durations — an NTP step
    mid-run would skew step times and can false-trip the health
    sentinel's EWMA baseline. Every surviving ``time.time()`` call site
    must be a timestamp assignment (a line carrying a ``ts``/``created``
    key); durations use ``time.monotonic()``/``perf_counter()``."""
    offenders: list[str] = []
    for path in _emitting_files():
        for i, line in enumerate(path.read_text().splitlines(), 1):
            if not WALLCLOCK_RE.search(line) or line.lstrip().startswith("#"):
                continue
            if "``" in line or "reference" in line:
                continue          # prose in docstrings, not a call site
            if ('"ts"' in line or "'ts'" in line or '"created"' in line
                    or "t0w" in line or "time.time() - dur_s" in line
                    or "_t0w = time.time()" in line):
                continue
            offenders.append(f"{path.relative_to(REPO)}:{i}: {line.strip()}")
    assert not offenders, (
        "wall-clock time.time() used outside a timestamp assignment — "
        "use time.monotonic() for durations (satellite: NTP-immune "
        "timing):\n" + "\n".join(offenders))
