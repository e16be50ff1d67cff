"""bdsan runtime sanitizers: lock-order witnesses + leak tracking +
crash diagnostics.  The dynamic half of the race/leak hunting layer
(docs/sanitizers.md); the static half is bdlint's ``wp-shared-state`` /
``lock-order`` whole-program analyses.

Gate: ``BYDB_SANITIZE=1`` (tests/conftest.py switches it on for the
whole pytest run).  ``install()`` is idempotent and does three things:

1. patches ``threading.Lock``/``RLock`` so package-created locks record
   acquisition-order witness edges mapped to their static declaration
   identities (lockwatch.py);
2. enables ``faulthandler`` so a wedged process dumps every thread's
   stack on SIGABRT/SIGSEGV (``arm_watchdog``/``disarm_watchdog``, the
   per-test-phase watchdog, also fail a unit of work that hangs);
3. exposes the leak-tracking primitives (leaks.py) the conftest
   thread-parity fixture and the stress tests build on.

Everything here is import-light until install() runs: the static lock
model (an AST pass over the package) loads once, lazily.
"""

from __future__ import annotations

from banyandb_tpu.utils.envflag import env_flag


def enabled() -> bool:
    return env_flag("BYDB_SANITIZE", default=False)


_installed = False


def install() -> bool:
    """Install the runtime sanitizers (idempotent).  Returns True when
    active after the call."""
    global _installed
    if _installed:
        return True
    import faulthandler

    from banyandb_tpu.sanitize import lockwatch

    lockwatch.install()
    faulthandler.enable()
    _installed = True
    return True


def installed() -> bool:
    return _installed


class WatchdogExpired(BaseException):
    """Raised in the main thread when a unit of work outlives its
    watchdog.  A BaseException, so an ``except Exception`` around the
    wait that hung cannot swallow it."""


def arm_watchdog(timeout_s: float, what: str, dump_fd: int) -> None:
    """Fail ``what`` if it is still running after ``timeout_s``: write
    every thread's stack to ``dump_fd`` and raise WatchdogExpired in the
    main thread.  A SIGALRM timer does it, so it interrupts sleep,
    Lock.acquire, Thread.join and Event.wait; the main thread must call
    this.  A wait no signal interrupts (a C call that never returns to
    the interpreter) is ended at 1.25 x ``timeout_s`` by faulthandler,
    which dumps the stacks and exits the process.  Re-arming replaces
    both timers."""
    import faulthandler
    import os
    import signal

    def expired(signum, frame):
        os.write(
            dump_fd,
            f"\n+++ bdsan watchdog: {what} still running after "
            f"{timeout_s:g} s; every thread's stack: +++\n".encode(),
        )
        faulthandler.dump_traceback(dump_fd, all_threads=True)
        raise WatchdogExpired(
            f"{what} still running after {timeout_s:g} s "
            "(BYDB_SANITIZE_WATCHDOG_S); the stacks are on stderr"
        )

    signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, timeout_s)
    faulthandler.dump_traceback_later(timeout_s * 1.25, exit=True, file=dump_fd)


def disarm_watchdog() -> None:
    import faulthandler
    import signal

    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, signal.SIG_DFL)
    faulthandler.cancel_dump_traceback_later()
