"""What the per-layer readers of the archive's import share: the
window's ``archive.shard`` spans (one a shard that counted, from the
first request of its file until the verified floor moved over it) and
the spans beneath them. A program that records none (the same reader
files run against a parent commit from before they existed) gives every
reader None."""

from __future__ import annotations

from . import progspans, stats

ROOT = "archive.shard"


def counted_shards(sources: dict):
    """-> (the window's ``archive.shard`` spans of shards that were
    installed, every complete span by id), or None."""
    events = [ev for ev in sources.get("spans") or ()
              if ev.get("ph") == "X"]
    roots = [ev for ev in events if ev["name"] == ROOT
             and not ev["args"].get("rejected")
             and not ev["args"].get("dropped")]
    if not roots:
        return None
    return roots, {ev["args"].get("span"): ev for ev in events}


def beneath(sources: dict, names) -> tuple[list, list] | None:
    """-> (the counted shards' spans, the spans of ``names`` that lie
    beneath one of them by their parent links), or None."""
    got = counted_shards(sources)
    if got is None:
        return None
    roots, by_id = got
    root_ids = {ev["args"].get("span") for ev in roots}
    picked = []
    for ev in progspans.complete(by_id.values(), names):
        parent = ev["args"].get("parent")
        for _hop in range(8):
            if parent is None or parent in root_ids:
                break
            up = by_id.get(parent)
            parent = up["args"].get("parent") if up is not None else None
        if parent in root_ids:
            picked.append(ev)
    return roots, picked


def share_of_shard(sources: dict, names):
    """100 x the seconds inside the spans of ``names`` over the seconds
    of the counted shards' ``archive.shard`` spans."""
    got = beneath(sources, names)
    if got is None:
        return None
    roots, picked = got
    total = progspans.seconds(roots)
    if not picked or total <= 0:
        return None
    return 100.0 * progspans.seconds(picked) / total


def _intervals(events) -> list:
    return sorted((ev["ts"], ev["ts"] + ev["dur"]) for ev in events)


def overlap_share(sources: dict):
    """100 x the seconds in which an ``archive.fetch`` and an
    ``archive.import`` ran at once over the window's seconds."""
    got = beneath(sources, ("archive.fetch", "archive.import"))
    window_s = (sources.get("counters") or {}).get("window_s")
    if got is None or not window_s:
        return None
    _roots, picked = got
    fetches = _intervals(progspans.complete(picked, ("archive.fetch",)))
    imports = _intervals(progspans.complete(picked, ("archive.import",)))
    if not fetches or not imports:
        return None
    both = stats.union_seconds(
        (max(a, c), min(b, d)) for a, b in fetches for c, d in imports
        if min(b, d) > max(a, c))
    return 100.0 * both / 1e6 / window_s


def import_cpu_share(sources: dict):
    """100 x what the importing thread ran (``cpu_us``) inside the
    ``archive.import`` spans over their wall seconds: what is missing
    from 100 the thread spent waiting, for the interpreter's lock, the
    disk or the device."""
    got = beneath(sources, ("archive.import",))
    if got is None:
        return None
    _roots, picked = got
    clocked = [ev for ev in picked if ev["args"].get("cpu_us") is not None]
    wall = sum(ev["dur"] for ev in clocked)
    if not clocked or wall <= 0:
        return None
    return 100.0 * sum(ev["args"]["cpu_us"] for ev in clocked) / wall
