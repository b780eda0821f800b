"""Cross-process doorbells: a futex on a 32-bit word in shared memory.

A ring waiter that has outlived its spin window sleeps in the kernel on
a doorbell word instead of napping in ``poll_interval_us`` quanta; the
publisher bumps the word and wakes it.  Linux futexes work across
processes on a ``MAP_SHARED`` mapping as long as the operation is not
``FUTEX_PRIVATE_FLAG``: the kernel keys the wait on the backing page, so
two processes (or two mappings in one process) meet on the same word.

Where the syscall is missing (not Linux, an unknown architecture, or
``ENOSYS`` from a sandbox) :data:`AVAILABLE` is False, found once at
import, and the ring falls back to its quantum naps.

Ordering: :func:`fence` is a full barrier between a plain store and a
later plain load (the Dekker pair of a publisher that stores the state,
bumps the word and then reads the waiter's mark).  It takes and drops a
lock: on x86-64 that is a ``lock``-prefixed instruction, a full barrier.
Where a lock orders only acquire and release (aarch64), a wake-up lost
to that is caught by the waiter's bounded sleep slice.  On the waiter
side the kernel itself orders the waiter's mark before its compare of
the word (``FUTEX_WAIT`` takes a full barrier before it reads the value).
"""
from __future__ import annotations

import ctypes
import platform
import sys
import threading

# syscall numbers of futex(2) by architecture
_SYS_FUTEX = {"x86_64": 202, "aarch64": 98}
# no FUTEX_PRIVATE_FLAG: the word lives in memory shared between processes
_FUTEX_WAIT, _FUTEX_WAKE = 0, 1
_WAKE_ALL = 0x7FFFFFFF


class _Timespec(ctypes.Structure):
    _fields_ = [("tv_sec", ctypes.c_long), ("tv_nsec", ctypes.c_long)]


def _syscall(lib):
    fn = lib.syscall
    fn.restype = ctypes.c_long
    fn.argtypes = [ctypes.c_long, ctypes.c_void_p, ctypes.c_int,
                   ctypes.c_uint, ctypes.c_void_p, ctypes.c_void_p,
                   ctypes.c_uint]
    return fn


def _load():
    """``(blocking, nonblocking, nr)``: the raw ``syscall`` entry called
    with the interpreter lock released (for waits) and held (for wakes:
    a thread that drops the lock around a short call can wait a whole
    switch interval to get it back), and the futex number; None when a
    probe wake (no waiters, so it returns 0) fails."""
    nr = _SYS_FUTEX.get(platform.machine())
    if nr is None or not sys.platform.startswith("linux"):
        return None
    try:
        blocking = _syscall(ctypes.CDLL(None, use_errno=True))
        nonblocking = _syscall(ctypes.PyDLL(None, use_errno=True))
    except (OSError, AttributeError):
        return None
    probe = ctypes.c_uint32(0)
    if nonblocking(nr, ctypes.addressof(probe), _FUTEX_WAKE, 1,
                   None, None, 0) < 0:
        return None
    return blocking, nonblocking, nr


_FUTEX = _load()

#: True where futex(2) answered the import-time probe
AVAILABLE = _FUTEX is not None

_FENCE = threading.Lock()


def fence() -> None:
    """Full memory barrier between the caller's earlier stores and its
    later loads (a lock round trip; see the module docstring)."""
    _FENCE.acquire()
    _FENCE.release()


def wait(addr: int, expected: int, timeout_s: float) -> None:
    """Sleep while the 32-bit word at ``addr`` still holds ``expected``,
    for at most ``timeout_s``.  Returns at once when the word differs;
    returns on a wake, a timeout or a signal alike, so the caller always
    re-checks its condition."""
    fn, _, nr = _FUTEX
    sec = int(timeout_s)
    ts = _Timespec(sec, int((timeout_s - sec) * 1e9))
    fn(nr, addr, _FUTEX_WAIT, expected & 0xFFFFFFFF, ctypes.addressof(ts),
       None, 0)


def wake(addr: int) -> None:
    """Wake every thread sleeping on the 32-bit word at ``addr``."""
    _, fn, nr = _FUTEX
    fn(nr, addr, _FUTEX_WAKE, _WAKE_ALL, None, None, 0)
