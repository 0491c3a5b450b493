"""Who writes a socket (ADR 019): the flush pass hands an idle writer's
burst to one native thread that never holds the interpreter
(``native/maxmq_sender.cpp``), so a burst's send, ~60-75 us of kernel
loopback work, leaves the event loop. The loop keeps every book of its
own: queue accounting, ``info``, ``write_progress``, the drain watchers.

Only where the code can see that it may: a plain TCP or Unix-socket
``_SelectorSocketTransport`` under a loaded native library. A TLS
transport (raw bytes on the fd would bypass the encryption), a writer
facade with no ``transport`` and a build without the library write
through the transport as before (``SocketSender.open`` returns None).

The rules, each for a guarantee (``Client`` keeps them):

* order: one FIFO a socket, and the loop writes through the transport
  only while the sender holds nothing for it (``idle``): the pass's
  ``_write_direct`` then hands over, else the writer task takes over,
  after ``wait_idle``; ``send_now`` appends to the FIFO while it holds
  bytes;
* back-pressure: the sender holds at most one burst a socket, and a
  short write is handed back (a spill event), whole and in order, to
  ``transport.write``, whose own buffer and writer watch take it on;
* the FIN: the sender writes to a dup of the socket, closed once its
  FIFO is empty after ``forget``, so closing the transport sends no FIN
  ahead of the sender's last byte.
"""

from __future__ import annotations

import asyncio
import os
import socket
from asyncio.selector_events import _SelectorSocketTransport

from .. import native

_FAMILIES = (socket.AF_INET, socket.AF_INET6, socket.AF_UNIX)


class SocketSender:
    """One broker's writer thread and the loop's side of it: the
    eventfd watch that collects its events (spills, errors, sockets
    gone idle) and the owner of each socket it writes."""

    def __init__(self, mod, loop: asyncio.AbstractEventLoop) -> None:
        self._core = core = mod.Sender()
        self._loop = loop
        self._spill, self._error = mod.SPILL, mod.ERROR
        self._owners: dict = {}       # handle -> Client
        self._leaving: set = set()    # forgotten, events still due
        self._waiters: dict = {}      # handle -> future: wait_idle
        self.closed = False
        # the hot path's calls, bound once: submit(handle, bufs) copies a
        # burst in; idle(handle) says the sender holds nothing for it;
        # kick() wakes the thread if it sleeps, once a pass
        self.submit = core.submit
        self.idle = core.idle
        self.kick = core.kick
        loop.add_reader(core.fileno(), self._on_events)

    @classmethod
    def start(cls, loop) -> SocketSender | None:
        """The broker's sender, or None without the native library or
        a selector loop to watch its eventfd from."""
        mod = native.sender_module()
        if mod is None:
            return None
        try:
            return cls(mod, loop)
        except (NotImplementedError, OSError, RuntimeError):
            return None

    def open(self, client) -> int | None:
        """A handle for ``client``'s socket, or None where its writer
        must keep the transport's path (TLS, facades, other loops)."""
        transport = getattr(client.writer, "transport", None)
        if self.closed or type(transport) is not _SelectorSocketTransport:
            return None
        sock = transport.get_extra_info("socket")
        if (sock is None or sock.family not in _FAMILIES
                or sock.type != socket.SOCK_STREAM):
            return None
        handle = self._core.open(sock.fileno())
        self._owners[handle] = client
        return handle

    async def wait_idle(self, handle: int) -> None:
        """Until the sender holds nothing for ``handle``: the writer
        task's turn at the socket."""
        while not self._core.watch(handle):
            fut = self._waiters.get(handle)
            if fut is None or fut.done():     # done: a cancelled waiter's
                fut = self._waiters[handle] = self._loop.create_future()
            await fut

    def forget(self, handle: int) -> None:
        """No more bytes for ``handle`` (``Client.stop``): its dup closes
        once the FIFO is empty, and with it the last reference to the
        socket, which is when the peer sees the FIN."""
        fut = self._waiters.pop(handle, None)
        if fut is not None and not fut.done():
            fut.set_result(None)
        if self._core.watch(handle):
            self._owners.pop(handle, None)
        else:
            self._leaving.add(handle)     # its last event pops it
        self._core.forget(handle)

    def _on_events(self) -> None:
        for handle, kind, payload in self._core.events():
            client = self._owners.get(handle)
            if client is not None:
                try:
                    if kind == self._spill:
                        # the rest of a short write, and whatever queued
                        # behind it: the transport's buffer and writer
                        # watch take it from here, in order
                        client.writer.transport.write(payload)
                    elif kind == self._error:
                        raise OSError(payload, os.strerror(payload))
                except Exception as exc:
                    client.sender_failed(exc)   # that client's alone
                if handle in self._leaving:
                    self._leaving.discard(handle)
                    del self._owners[handle]
            fut = self._waiters.pop(handle, None)
            if fut is not None and not fut.done():
                fut.set_result(None)

    def stats(self) -> dict:
        bursts, spills, errors, busy, wakes = self._core.stats()
        return {"bursts": bursts, "spills": spills, "errors": errors,
                "busy_seconds": busy, "wakes": wakes}

    def close(self) -> None:
        """Stop the thread once what it holds is written (the broker's
        clients are stopped by then); every dup closes."""
        if self.closed:
            return
        self.closed = True
        self._loop.remove_reader(self._core.fileno())
        self._core.close()
        self._owners.clear()
        self._leaving.clear()
        for fut in self._waiters.values():
            if not fut.done():
                fut.set_result(None)
        self._waiters.clear()
