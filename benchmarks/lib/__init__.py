"""The benchmark's own yardstick: nothing here names a cell, a model or a metric."""
