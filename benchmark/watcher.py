"""The benchmark's clock: a thread that reads a counter and stamps the host's
clock each time its value changes.

For a training job the counter is ``trainer.num_updates``.  The trainer
dispatches a copy of the on-device commit count behind every epoch's program
(``ParameterServer.track``); reading it waits for that copy, so the read
returns when the epoch has finished on the device.  The training thread is
never made to wait.
"""

import threading
import time


class Watcher:
    """``stamps`` is a list of ``(seconds on time.perf_counter, count)``, one
    per change seen.  ``on_change(count, t)`` callbacks run on the watcher's
    thread and must be quick."""

    def __init__(self, read, poll_s=0.001, clock=time.perf_counter):
        self._read = read
        self._poll_s = poll_s
        self._clock = clock
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, name="bench-watcher",
                                        daemon=True)
        self.stamps = []
        self.callbacks = []
        self.error = None

    def start(self):
        self._thread.start()
        return self

    def _poll(self, last):
        try:
            count = self._read()
        except TypeError:
            # the counter's holder swapped its live copy for the final count
            # between its own check and its read; the next read sees it
            return last
        if count != last:
            now = self._clock()
            self.stamps.append((now, count))
            for callback in self.callbacks:
                callback(count, now)
        return count

    def _loop(self):
        last = 0
        try:
            while not self._stop.is_set():
                last = self._poll(last)
                self._stop.wait(self._poll_s)
            self._poll(last)  # the final count, once the job has returned
        except BaseException as error:  # surfaced by stop(); never swallowed
            self.error = error

    def stop(self, timeout=60.0):
        self._stop.set()
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise RuntimeError("the watcher did not stop")
        if self.error is not None:
            raise self.error
        return self.stamps


def epoch_times(stamps, commits_per_epoch, epochs):
    """Completion time of each epoch: the first stamp at which the count had
    reached ``commits_per_epoch * (k + 1)``.  An epoch whose count was never
    seen on its own (the watcher was late and saw two at once) gets the time
    at which it was first known to be complete.  None for an epoch that the
    count never reached."""
    times = []
    for k in range(epochs):
        need = commits_per_epoch * (k + 1)
        times.append(next((t for t, count in stamps if count >= need), None))
    return times
