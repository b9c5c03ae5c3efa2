"""Named device phases of the index steps, and their device time in a
profiler capture.

The jitted steps (``core/index.py``, ``core/snapshots.py``) wrap each
phase in ``jax.named_scope`` and the Pallas kernels carry a
``pallas_call(name=...)``, so every XLA op's ``op_name`` metadata holds
the phase it came from (``jit(query_step)/vmap(main_lookup)/...``).
These names are stable across builds, unlike the compiler's fusion
numbers: :data:`STEPS` lists them per step, :data:`KERNELS` the kernels.

A TPU profiler capture (``.xplane.pb``) keeps each op's ``op_name`` as
the ``tf_op`` stat of its event *metadata*, which
``jax.profiler.ProfileData`` does not expose; :func:`op_paths` reads it
from the protobuf wire format.  The capture keys that metadata by the
op's HLO text, so an op whose text is the same in two programs (the
same helper at the same shapes) carries one program's path.
:func:`device_time` puts each op's device self time down to the
innermost named phase in its path (:func:`phase_of`), under the program
whose run it falls in; time with no phase is :data:`UNSCOPED`.  Self
time, because a control-flow op's event (``%while.85``) spans the
events of the ops its body runs.
"""
from __future__ import annotations

import bisect
import re

STEPS = {
    "query_step": ("hash", "hot_descent", "sealed_probe", "dedupe",
                   "main_lookup", "ring_lookup", "rank"),
    "insert_step": ("store_alloc", "main_insert", "lsh_insert", "flags"),
    "delete_step": ("main_lookup", "ring_lookup", "lsh_unlink",
                    "main_unlink", "store_free", "tombstone", "flags"),
    "merge_step": ("merge_filter", "merge_sort", "reseal"),
    "seal_step": ("reseal",),
}
KERNELS = ("lsh_hash", "gather_rank", "gather_rank_staged")
NAMES = frozenset(p for ps in STEPS.values() for p in ps) | set(KERNELS)
UNSCOPED = "(unscoped)"

_WRAPPED = re.compile(r"[\w.]+\((.*)\)")     # vmap(x), jit(x), ...
_SUFFIX = re.compile(r"\(\d+\)$")            # jit_query_step(<hash>)


def phase_of(op_path: str) -> str:
    """The innermost phase or kernel name in an ``op_name`` path, seen
    through transform wrappers (``vmap(vmap(main_lookup))``)."""
    for part in reversed(op_path.split("/")):
        while part not in NAMES:
            m = _WRAPPED.fullmatch(part)
            if m is None:
                break
            part = m.group(1)
        if part in NAMES:
            return part
    return UNSCOPED


# -- the protobuf wire format, as far as XSpace metadata needs it --------
def _varint(b, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """(field number, value) of a serialized message: an int for a
    varint, a memoryview for every other wire type."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_paths(xspace: bytes) -> dict[str, str]:
    """``{op event name: op_name path}`` over the device planes of a
    serialized XSpace (an ``.xplane.pb`` file's bytes)."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:                                   # XSpace.planes
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:                              # XPlane.name
                name = _text(v)
            elif pf == 4:                            # event_metadata
                events.append(v)
            elif pf == 5:                            # stat_metadata
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        if not name.startswith("/device:"):
            continue
        tf_op = {k for k, s in stat_names.items() if s == "tf_op"}
        for v in events:
            entry = dict(_fields(v))
            ev_name, path = None, None
            for mf, mv in _fields(entry.get(2, b"")):
                if mf == 2:                          # XEventMetadata.name
                    ev_name = _text(mv)
                elif mf == 5:                        # XEventMetadata.stats
                    stat = dict(_fields(mv))
                    if stat.get(1) in tf_op and 5 in stat:
                        path = _text(stat[5]).rsplit(":", 1)[0]
            if ev_name and path:
                out[ev_name] = path
    return out


def device_time(xspace: bytes, window: tuple[int, int] | None = None
                ) -> dict[tuple[str, str], list]:
    """``{(program, phase): [device seconds, runs]}``: the self time of
    each ``XLA Ops`` event of a device plane (its interval, clipped to
    ``window`` in ``ProfileData`` nanoseconds, less that of the events
    nested in it) goes to the program run (``XLA Modules`` event) it
    starts in and to the phase of its op path; ``runs`` counts that
    program's runs in the window.  Seconds average over the chips."""
    from jax.profiler import ProfileData
    paths = op_paths(xspace)
    pd = ProfileData.from_serialized_xspace(xspace)
    w0, w1 = window or (float("-inf"), float("inf"))
    acc: dict = {}
    runs: dict = {}
    n_chips = 0
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if not plane.name.startswith("/device:") or "XLA Ops" not in lines:
            continue
        n_chips += 1
        mods = []
        for ev in (lines["XLA Modules"].events if "XLA Modules" in lines
                   else ()):
            if min(ev.end_ns, w1) > max(ev.start_ns, w0):
                name = _SUFFIX.sub("", ev.name)
                mods.append((ev.start_ns, ev.end_ns, name))
                runs[name] = runs.get(name, 0) + 1
        mods.sort()
        starts = [m[0] for m in mods]
        ops = sorted(((max(ev.start_ns, w0), -min(ev.end_ns, w1), ev.name)
                      for ev in lines["XLA Ops"].events), key=lambda o: o[:2])
        open_ops: list = []               # [end, key] of enclosing ops
        for s, neg_e, name in ops:
            e = -neg_e
            if e <= s:
                continue
            while open_ops and open_ops[-1][0] <= s:
                open_ops.pop()
            j = bisect.bisect_right(starts, s) - 1
            prog = mods[j][2] if j >= 0 and s < mods[j][1] else "?"
            key = (prog, phase_of(paths.get(name, "")))
            dur = (e - s) * 1e-9
            acc[key] = acc.get(key, 0.0) + dur
            if open_ops:                  # not the parent's own time
                end, parent = open_ops[-1]
                acc[parent] -= (min(e, end) - s) * 1e-9
            open_ops.append([e, key])
    return {k: [v / max(n_chips, 1), runs.get(k[0], 0) // max(n_chips, 1)]
            for k, v in acc.items()}
