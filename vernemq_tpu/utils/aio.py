"""asyncio helpers shared by the listeners."""

from __future__ import annotations

import asyncio
from typing import Iterable, Optional

# Server.wait_closed() waits for every accepted connection to finish, so
# a listener's stop() must first close the connections it accepted —
# and must not hang on one whose peer never lets go.
STOP_TIMEOUT = 5.0


async def close_server(server: Optional[asyncio.AbstractServer],
                       writers: Iterable[asyncio.StreamWriter],
                       timeout: float = STOP_TIMEOUT) -> None:
    """Stop accepting, abort the listener's live connections, then wait
    (bounded) for their handlers to unwind."""
    if server is None:
        return
    server.close()
    for w in list(writers):
        try:
            w.transport.abort()
        except Exception:
            pass
    try:
        await asyncio.wait_for(server.wait_closed(), timeout)
    except asyncio.TimeoutError:
        pass
