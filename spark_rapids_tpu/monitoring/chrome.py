"""Chrome trace-event rendering of the flight-recorder stream.

The output is the Trace Event Format's JSON object flavor
(``{"traceEvents": [...]}``) that chrome://tracing and Perfetto's legacy
importer load directly. Mapping:

- ``pid`` = query id, with a ``process_name`` metadata event naming the
  track ``query <N>`` — so concurrent queries render as separate
  process groups and "what did query 7 do to query 8" is one screen.
- ``tid`` = recording thread, named from the live thread names
  (``srt-prefetch-*``, ``srt-stage-*``, ``srt-watchdog-*``, the collect
  thread) — scheduler queueing, host prefetch, device dispatch, shuffle
  spool and recovery rework land on distinct tracks.
- spans are ``"X"`` complete events (ts/dur in microseconds, as the
  format requires), instants are ``"i"`` thread-scoped events.
- the format has no field for a span's cause, so each event's ``args``
  carry ``sid`` / ``parent`` (the recorder's span ids) and a span's
  ``self_us`` (its duration less its same-thread children).
"""

from __future__ import annotations

from typing import Dict, List

from spark_rapids_tpu.monitoring.recorder import self_ns


def to_chrome(events: List[tuple], thread_names: Dict[int, str],
              process_tag: str = "") -> dict:
    """Render recorder event tuples into one Chrome trace document.
    ``process_tag`` prefixes every process track name — cluster worker
    processes pass ``worker <wid>`` so their exports stay attributable
    when several per-process traces are viewed side by side."""
    prefix = f"{process_tag} " if process_tag else ""
    trace: List[dict] = []
    seen_pids = set()
    seen_tids = set()
    own = self_ns(events)
    for e in events:
        ph, name, cat, ts, dur, tid, qid, args, sid, parent = e
        if qid not in seen_pids:
            seen_pids.add(qid)
            trace.append({"ph": "M", "name": "process_name", "pid": qid,
                          "args": {"name": f"{prefix}query {qid}"}})
            trace.append({"ph": "M", "name": "process_sort_index",
                          "pid": qid, "args": {"sort_index": qid}})
        if (qid, tid) not in seen_tids:
            seen_tids.add((qid, tid))
            trace.append({"ph": "M", "name": "thread_name", "pid": qid,
                          "tid": tid,
                          "args": {"name": thread_names.get(
                              tid, f"thread-{tid}")}})
        ev = {"ph": ph, "name": name, "cat": cat, "pid": qid, "tid": tid,
              "ts": ts / 1e3}
        ev["args"] = dict(args or (), parent=parent)
        if ph == "X":
            ev["dur"] = (dur or 0) / 1e3
            ev["args"].update(sid=sid, self_us=own[sid] / 1e3)
        else:
            ev["s"] = "t"
        trace.append(ev)
    return {"traceEvents": trace, "displayTimeUnit": "ms"}


_WORKER_PID_STRIDE = 1_000_000


def to_chrome_cluster(driver_events: List[tuple],
                      driver_threads: Dict[int, str],
                      worker_groups: Dict[str, tuple],
                      process_tag: str = "") -> dict:
    """ONE merged Perfetto document for a distributed query: the
    driver's rings render as usual, then each worker's shipped ring
    (the CDONE piggyback) is appended under its own process tracks.
    Worker ``k``'s pids are offset by ``(k+1) * 1_000_000`` so a
    worker's ring-0 events never collide with the driver's query
    tracks, while its ``process_name`` metadata keeps the worker tag
    ("worker w0 query 3"). ``worker_groups`` maps wid ->
    ``(events, thread_names, tag)`` — the shape the coordinator
    stashes in ``ctx.cache["cluster_worker_events"]``."""
    doc = to_chrome(driver_events, driver_threads, process_tag)
    trace = doc["traceEvents"]
    for k, wid in enumerate(sorted(worker_groups)):
        events, threads, tag = worker_groups[wid]
        base = (k + 1) * _WORKER_PID_STRIDE
        for ev in to_chrome(events, threads, tag)["traceEvents"]:
            ev = dict(ev)
            ev["pid"] = base + ev["pid"]
            if ev.get("name") == "process_sort_index":
                ev["args"] = {"sort_index": ev["pid"]}
            trace.append(ev)
    return doc
