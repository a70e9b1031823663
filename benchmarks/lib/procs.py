"""Child processes of a run: started in the run's own process group,
their output copied to ours line by line, stopped and waited for."""

from __future__ import annotations

import signal
import subprocess
import sys
import threading
import time


class Child:
    def __init__(self, name: str, argv, env, cwd, stdin=False, on_line=None):
        self.name = name
        self.lines: list = []
        self._cond = threading.Condition()
        self._on_line = on_line
        self.proc = subprocess.Popen(
            argv, env=env, cwd=cwd, text=True, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            stdin=subprocess.PIPE if stdin else subprocess.DEVNULL)
        self._pump = threading.Thread(target=self._copy, daemon=True,
                                      name=f"pump-{name}")
        self._pump.start()

    def _copy(self) -> None:
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            if self._on_line:
                self._on_line(line)
            with self._cond:
                self.lines.append(line)
                self._cond.notify_all()
            if not line.startswith(("@@", "RESULT ")):
                print(f"  [{self.name}] {line}", file=sys.stderr, flush=True)

    def wait_for(self, needle: str, timeout: float, after: int = 0,
                 anywhere: bool = False):
        """The first line from index ``after`` on that starts with
        ``needle`` (or, with ``anywhere``, holds it); None at the timeout
        or when the child has ended."""
        deadline = time.monotonic() + timeout
        i = after
        with self._cond:
            while True:
                while i < len(self.lines):
                    line = self.lines[i]
                    if needle in line if anywhere else \
                            line.startswith(needle):
                        return line
                    i += 1
                left = deadline - time.monotonic()
                if left <= 0:
                    return None
                if self.proc.poll() is not None and \
                        not self._pump.is_alive():
                    return None
                self._cond.wait(timeout=min(left, 0.5))

    def send(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def finish(self, timeout: float) -> int:
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
        rc = self.proc.wait()
        self._pump.join(timeout=5.0)
        return rc

    def terminate(self, timeout: float = 60.0) -> int:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        return self.finish(timeout)
