"""The yardstick: published peaks of each chip and the work of each kernel
and program, computed from shapes alone."""
