"""Named-axis helpers shared by ops/ and parallel/."""

from __future__ import annotations

from jax import lax

# Size of a bound named mesh axis (or product over a tuple of axes), as a
# static Python int inside a trace.
axis_size = lax.axis_size
