from . import kernel, ops
