"""The benchmark's yardstick: nothing here imports the program."""
