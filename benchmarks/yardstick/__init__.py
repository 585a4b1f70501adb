"""The benchmark's own yardstick: traffic generation, arithmetic, the
trace reduction, peaks and the manifest check. Nothing here is imported
by the program, and nothing here imports a file a later PR may change
except the program's public entry points."""
