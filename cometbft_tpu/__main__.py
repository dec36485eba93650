import sys

# The lock-order witness must patch threading.Lock/RLock BEFORE the
# framework's import closure creates its module-level locks (tracing
# rings, metrics hub, ...) — importing .cli below drags all of that in.
# .analysis.lockwitness itself only touches the stdlib.
from .analysis import lockwitness

lockwitness.maybe_install()

# Persistent XLA compile cache (utils/compilecache: where
# JAX_COMPILATION_CACHE_DIR says, else the checkout's tests/.jax_cache),
# configured before any kernel compiles so a restarted node loads its
# executables instead of recompiling them.
from .utils import compilecache  # noqa: E402

compilecache.enable()

from .cli import main  # noqa: E402

sys.exit(main())
