"""``python -m pilosa_tpu.cli server`` as the benchmark's one child.

Copied from ``chip_smoke.py`` (``ServerChild``): the child is started
before this process touches jax, because a chip belongs to one process.
Its output goes to files; jax's compile log (``JAX_LOG_COMPILES=1``) is
read back from its stderr.
"""

from __future__ import annotations

import os
import re
import signal
import subprocess
import sys
import time


class ServerFailure(Exception):
    """The child did not start, serve or stop as it must."""


class ServerChild:
    """A context manager: however the block ends, the child is not left
    running."""

    def __init__(self, root: str, out_dir: str, data_dir: str, env: dict, args=()):
        self.out_path = os.path.join(out_dir, "server.out")
        self.err_path = os.path.join(out_dir, "server.err")
        self._out = open(self.out_path, "wb")
        self._err = open(self.err_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.cli", "server",
             "--data-dir", data_dir, "--host", "127.0.0.1:0", *args],
            stdout=self._out, stderr=self._err, cwd=root, env=env,
        )

    def __enter__(self) -> "ServerChild":
        return self

    def __exit__(self, *exc) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._out.close()
        self._err.close()

    def wait_ready(self, timeout: float) -> str:
        """Block until the startup line; returns host:port."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with open(self.out_path, "rb") as f:
                m = re.search(rb"serving on http://(\S+)", f.read())
            if m:
                return m.group(1).decode()
            if self.proc.poll() is not None:
                raise ServerFailure(
                    f"server exited with code {self.proc.returncode} before "
                    f"serving: {self.err_tail()}")
            time.sleep(0.1)
        raise ServerFailure(f"server not ready after {timeout:.0f} s: {self.err_tail()}")

    def err_tail(self, n: int = 1500) -> str:
        with open(self.err_path, "rb") as f:
            f.seek(max(0, os.path.getsize(self.err_path) - n))
            return f.read().decode(errors="replace")

    def err_size(self) -> int:
        return os.path.getsize(self.err_path)

    def compile_log(self, start: int) -> dict:
        """What jax logged since byte ``start`` of the child's stderr:
        compilations, their seconds, persistent-cache hits, program names."""
        with open(self.err_path, "rb") as f:
            f.seek(start)
            text = f.read().decode(errors="replace")
        secs = [float(x) for x in re.findall(
            r"Finished XLA compilation of .* in ([0-9.]+) sec", text)]
        shaped = re.findall(r"Compiling jit\((\w+)\) with global shapes and types \((.*?)\)\. Argument", text)
        return {"compilations": len(secs), "compile_seconds": round(sum(secs), 3),
                "cache_hits": len(re.findall(r"Persistent compilation cache hit", text)),
                "programs": sorted(set(re.findall(r"Compiling jit\((\w+)\)", text))),
                # programs over arrays of three dimensions or more: the pool, a block, a Gram
                "shapes": sorted({f"{name}{re.findall(r'\w+\[[0-9,]*\]', args)}".replace("'", "")
                                  for name, args in shaped if re.search(r"\[\d+,\d+,\d+", args)})}

    def stop(self, timeout: float = 120.0) -> int:
        """SIGTERM, wait; the exit code.  A child that will not stop is
        killed by ``__exit__`` and reported here."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                raise ServerFailure(f"server ignored SIGTERM for {timeout:.0f} s")
        return self.proc.returncode
