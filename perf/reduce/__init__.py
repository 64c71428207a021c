"""The yardstick: trace -> reduction, the table of peaks, FLOPs from shapes."""
