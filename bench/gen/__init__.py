"""Traffic generators, one module per ``generator`` named in a traffic file."""
