"""Traffic source: Linux-audit SYSCALL lines, packed into wire frames.

``make_line`` is a copy of ``detectmateservice_tpu/loadgen/corpus.py`` (the
upstream demo's stream: 5 normal and 3 anomalous ``comm``s, one template).
The copy is the yardstick; the original stays the program's.

From a seed the harness builds, once, during set-up:

* training lines, all normal (``make_line`` as the program has it);
* a pool of distinct lines, ``anomaly_share`` of them anomalous (the same
  count for every seed, at seeded places), packed into frames that the window
  only re-sends, cycling the pool. A pool line takes its time, serial and pid
  from training lines (two drawn at random), so a normal pool line holds no
  token the fit has not seen and an anomalous one differs by its ``comm``
  and ``exe`` alone. With fresh values in those fields every line carries
  unseen tokens, and the shipped ``mlp`` configuration alerts on about a
  third of the normal lines (PERF.md, PR 23): the output stage then sets the
  pace and the cell measures alert handling, not the pipeline;
* warm-up lines, all normal, with ids of their own.

A pool line's ``logID`` is its pool index in fixed width, so an alert at the
sink names the line, and the k-th alert for one index belongs to cycle k.
"""
from __future__ import annotations

import random
from dataclasses import dataclass
from typing import Callable, List, Sequence

NORMAL_COMMS = [
    ("cron", "/usr/sbin/cron", 0),
    ("sshd", "/usr/sbin/sshd", 0),
    ("systemd", "/lib/systemd/systemd", 0),
    ("bash", "/bin/bash", 1000),
    ("python3", "/usr/bin/python3", 1000),
]
ANOMALOUS_COMMS = [
    ("nc", "/tmp/.hidden/nc", 1000),
    ("xmrig", "/dev/shm/xmrig", 33),
    ("sh", "/var/www/uploads/sh", 33),
]
AUDIT_LOG_FORMAT = "type=<Type> msg=audit(<Time>): <Content>"
AUDIT_TEMPLATE = ("arch=<*> syscall=<*> success=<*> exit=<*> pid=<*> "
                  "uid=<*> comm=<*> exe=<*>")
_HEADER = "type=SYSCALL msg=audit({ts}.{ms:03d}:{serial}): "

# batch frame of the program's wire format (engine/framing.py): magic,
# varint count, then varint length + bytes per message
_MAGIC = b"\xd7DM\x01"

POOL_ID_WIDTH = 6


def _render(stamp: int, pid: int, rng: random.Random, anomaly: bool) -> str:
    """One line; ``stamp`` gives time and serial as ``make_line`` derives
    them from its index. The draws keep ``make_line``'s order."""
    comm, exe, uid = rng.choice(ANOMALOUS_COMMS if anomaly else NORMAL_COMMS)
    syscall = rng.choice([59, 42, 2]) if not anomaly else 59
    if pid is None:
        pid = rng.randint(300, 9000)
    return (
        _HEADER.format(ts=1_753_800_000 + stamp, ms=stamp % 1000,
                       serial=9000 + stamp)
        + f'arch=c000003e syscall={syscall} success=yes exit=0 '
        f'pid={pid} '
        f'uid={uid} comm="{comm}" exe="{exe}"'
    )


def make_line(i: int, rng: random.Random, anomaly: bool) -> str:
    return _render(i, None, rng, anomaly)


def _pid_of(line: str) -> int:
    return int(line.split(" pid=", 1)[1].split(" ", 1)[0])


def _put_varint(out: bytearray, value: int) -> None:
    while True:
        bits = value & 0x7F
        value >>= 7
        if value:
            out.append(bits | 0x80)
        else:
            out.append(bits)
            return


def pack_frame(messages: Sequence[bytes]) -> bytes:
    out = bytearray(_MAGIC)
    _put_varint(out, len(messages))
    for msg in messages:
        _put_varint(out, len(msg))
        out += msg
    return bytes(out)


def pack_frames(messages: Sequence[bytes], frame_lines: int) -> List[bytes]:
    return [pack_frame(messages[i:i + frame_lines])
            for i in range(0, len(messages), frame_lines)]


@dataclass
class Pool:
    lines: List[str]            # the audit lines, by pool index
    messages: List[bytes]       # serialized LogSchema, by pool index
    anomalous: List[int]        # sorted pool indices of the anomalous lines
    frames: List[bytes]         # packed frames, frame f holds lines f*L..
    frame_lines: int

    def pool_id(self, index: int) -> str:
        return f"{index:0{POOL_ID_WIDTH}d}"


Serialize = Callable[[str, str], bytes]   # (logID, line) -> LogSchema bytes


def build_pool(seed: int, n_lines: int, frame_lines: int,
               anomaly_share: float, train_lines: Sequence[str],
               train_first_index: int, serialize: Serialize) -> Pool:
    if n_lines % frame_lines:
        raise ValueError("pool size must be a whole number of frames")
    rng = random.Random(f"pool:{seed}")
    n_anomalous = round(n_lines * anomaly_share)
    anomalous = sorted(rng.sample(range(n_lines), n_anomalous))
    marks = set(anomalous)
    pids = [_pid_of(line) for line in train_lines]
    lines, taken = [], set()
    for i in range(n_lines):
        while True:
            line = _render(train_first_index + rng.randrange(len(pids)),
                           rng.choice(pids), rng, i in marks)
            if line not in taken:
                break
        taken.add(line)
        lines.append(line)
    messages = [serialize(f"{i:0{POOL_ID_WIDTH}d}", line)
                for i, line in enumerate(lines)]
    return Pool(lines, messages, anomalous,
                pack_frames(messages, frame_lines), frame_lines)


def normal_lines(seed: int, tag: str, n: int, first_index: int) -> List[str]:
    """``n`` all-normal lines, line *k* from ``make_line(first_index + k)``."""
    rng = random.Random(f"{tag}:{seed}")
    return [make_line(first_index + k, rng, False) for k in range(n)]


def tagged_messages(tag: str, lines: Sequence[str],
                    serialize: Serialize) -> List[bytes]:
    """Messages with ids ``<tag><k>``, apart from the pool's."""
    return [serialize(f"{tag}{k:05d}", line) for k, line in enumerate(lines)]
