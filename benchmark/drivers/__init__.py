"""Traffic drivers, one module per ``driver`` named in a traffic file."""
