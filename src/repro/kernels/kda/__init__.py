from . import ops, ref, kernel
