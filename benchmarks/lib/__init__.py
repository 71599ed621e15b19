"""The yardstick: what every cell shares and no later PR may change."""
