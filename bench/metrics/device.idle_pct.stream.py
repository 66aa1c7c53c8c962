"""Share of the window with no op on the device (`lib/readers.idle_pct`);
the stream cell, which reports `chunk_p95_ms`."""

from lib.readers import idle_pct as read  # noqa: F401
